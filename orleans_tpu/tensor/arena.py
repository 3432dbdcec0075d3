"""GrainArena: the stacked state store for one vector grain type.

The arena is the tensor-path Catalog + ActivationDirectory (reference:
Catalog.cs:43, ActivationDirectory.cs:33): an activation is a *row*; the
host keeps the key→row index (the local directory partition) and the device
holds the state columns.  Row blocks are assigned to mesh shards by grain
key hash, so "which device owns this grain" is the same stable function the
silo ring uses — the directory IS the sharding map (BASELINE.json north
star).

Auto-activation: resolving an unseen key allocates a row in the key's home
shard block and initializes its columns from the declared field inits —
the batched analog of GetOrCreateActivation (reference: Catalog.cs:411).
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from orleans_tpu.hashing import stable_hash_u64
from orleans_tpu.tensor.profiler import host_read
from orleans_tpu.tensor.vector_grain import StateField, VectorGrainInfo


class ArenaFullError(RuntimeError):
    pass


@jax.jit
def _touch_kernel(last_use_dev, rows, tick):
    # mode="drop" only drops OUT-OF-RANGE indices; -1 (unresolved miss)
    # would wrap to the last row and pin it hot forever, so remap negatives
    # past capacity where the scatter really does drop them
    rows = jnp.where(rows < 0, last_use_dev.shape[0], rows)
    return last_use_dev.at[rows].max(tick, mode="drop")


@jax.jit
def _touch_dense_kernel(last_use_dev, segments, tick):
    """Pull-mode delivery touch: rows holding edges (non-empty offset
    ranges) stamp in one elementwise pass — never a lane-sized
    scatter-max (tensor/streams_plane.py keeps that path scatter-free
    end to end)."""
    live = segments[1:] > segments[:-1]
    return jnp.maximum(last_use_dev, jnp.where(live, tick, 0))


@jax.jit
def _idle_mask_kernel(last_use_dev, last_use_host, live, cutoff):
    """Victim selection stays on device: merge both use clocks with one
    vectorized compare; only the boolean victim mask (1 byte/row) crosses
    to the host — never the full clock columns or any state field."""
    return live & (jnp.maximum(last_use_dev, last_use_host) < cutoff)


@jax.jit
def _spread_replicas_kernel(prim, counts, table, rows):
    """Scatter resolved rows across a hot grain's replica set: lanes
    whose row is a replicated PRIMARY re-point to one of the grain's
    replica rows by lane hash (deterministic — the host twin
    ``spread_rows_host`` computes the identical choice).  ``prim`` is the
    sorted primary rows pow2-padded with an int32 sentinel, ``counts``
    the per-group replica count (pad 1, so the modulus never divides by
    zero) and ``table`` the [groups, KMAX] replica row table (-1 pad).
    Non-replicated lanes (and misses, rows < 0) pass through unchanged —
    the common no-replica case never calls this at all."""
    lanes = jax.lax.iota(jnp.uint32, rows.shape[0])
    idx = jnp.clip(jnp.searchsorted(prim, rows), 0, prim.shape[0] - 1)
    hit = (prim[idx] == rows) & (rows >= 0)
    h = (lanes * jnp.uint32(2654435761)) >> jnp.uint32(8)
    choice = (h % counts[idx].astype(jnp.uint32)).astype(jnp.int32)
    alt = table[idx, choice]
    return jnp.where(hit & (alt >= 0), alt, rows)


def _pow2_pad(rows: np.ndarray, fill: int) -> np.ndarray:
    """Pad an index vector to the next power of two with ``fill`` —
    data-dependent row counts would otherwise compile one eager device
    gather/scatter per distinct length; pow2 padding bounds the compile
    set to O(log n).  ``fill`` is row 0 for gathers (result sliced back
    to the real length) or ``capacity`` for mode="drop" scatters."""
    pad = np.full(1 << max(0, len(rows) - 1).bit_length(), fill, np.int32)
    pad[:len(rows)] = rows
    return pad


def _hash_keys_u64(keys: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 matching hashing.stable_hash_u64, so host row
    assignment and any device-side bucketing agree."""
    x = keys.astype(np.uint64)
    x = x + np.uint64(0x9E3779B97F4A7C15)
    z = x
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def shard_of_keys(keys: np.ndarray, n_shards: int) -> np.ndarray:
    """THE device-shard-of-key function — the mesh-granularity twin of
    the silo ring's owner lookup (runtime/ring.py re-exports this as
    ``device_shard_of_keys``): every consumer of "which shard block
    holds this grain" — arena row allocation, the exchange's
    destination bucketing (``rows // shard_capacity``, which agrees by
    construction since rows are allocated in the key's home block), and
    the multichip bench's ratio construction — derives from this one
    hash.  The directory IS the sharding map, enforced by the agreement
    property test (tests/test_cross_shard.py)."""
    return (_hash_keys_u64(np.asarray(keys, dtype=np.int64))
            % np.uint64(max(1, n_shards))).astype(np.int64)


# -- wide (64-bit) key support ------------------------------------------------
# Device int64 needs jax x64 mode, so a wide key rides the mesh as TWO
# int32 words (reference key breadth: UniqueKey.cs:34 — two 64-bit words).
# Routing hashes the words into a 30-bit bucket space (the int32 padding
# sentinel can then never collide with a real hash) and verifies bucket
# candidates against the full words on device.

def split_wide_keys(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """int64[n] → (hi int32[n], lo int32[n]) bit-pattern words."""
    u = np.asarray(keys).astype(np.uint64)
    hi = (u >> np.uint64(32)).astype(np.uint32).view(np.int32)
    lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)
    return hi, lo


def join_wide_keys(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """(hi, lo) int32 words → int64 keys (bit-pattern inverse)."""
    u = (np.asarray(hi).view(np.uint32).astype(np.uint64) << np.uint64(32)) \
        | np.asarray(lo).view(np.uint32).astype(np.uint64)
    return u.astype(np.int64)


def mix32_np(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """30-bit bucket hash of a wide key's words; MUST stay bit-identical
    to the device version (engine._mix32_dev)."""
    h = (np.asarray(hi).view(np.uint32) * np.uint32(0x85EBCA6B)) \
        ^ (np.asarray(lo).view(np.uint32) * np.uint32(0xC2B2AE35))
    h = h ^ (h >> np.uint32(15))
    h = h * np.uint32(0x27D4EB2F)
    h = h ^ (h >> np.uint32(13))
    return (h & np.uint32(0x3FFFFFFF)).astype(np.int32)


class GrainArena:

    def __init__(self, info: VectorGrainInfo, capacity: int = 1024,
                 n_shards: int = 1, sharding: Optional[Any] = None,
                 store: Optional[Any] = None) -> None:
        self.info = info
        # VectorStore (tensor/persistence.py): activation reads persisted
        # rows (stage-2 analog, reference: Catalog.cs:731), eviction and
        # checkpoint write them back
        self.store = store
        self.evicted_count = 0
        self.restored_count = 0
        self.migrated_count = 0
        self.n_shards = max(1, n_shards)
        # capacity must divide evenly into shard blocks
        per_shard = max(1, -(-capacity // self.n_shards))
        self.shard_capacity = per_shard
        self.capacity = per_shard * self.n_shards
        self.sharding = sharding

        self.state: Dict[str, jnp.ndarray] = {}
        self._init_state_columns(self.capacity)
        # double-buffer flips: times the engine swapped the live columns
        # for a program's outputs (adopt_state) — with donated inputs
        # the old buffers are gone the moment the swap happens
        self.state_flips = 0
        # bumped whenever rows move (growth/repack); consumers holding
        # resolved row vectors must re-resolve on mismatch
        self.generation = 0
        # bumped whenever rows are FREED without moving (free-list
        # deactivation preserves the generation — surviving rows stay
        # put, so caches over live keys remain valid).  Consumers holding
        # resolved rows check BOTH: a generation mismatch means rows
        # moved (full re-resolve); an epoch-only mismatch means some rows
        # were freed — a cheap liveness re-check suffices, and only
        # caches that actually reference an evicted key pay a re-resolve.
        self.eviction_epoch = 0

        # host-side directory partition: key → row
        self._key_of_row = np.full(self.capacity, -1, dtype=np.int64)
        self._shard_next = np.zeros(self.n_shards, dtype=np.int64)
        # live-migration placement pins (key → shard): keys moved off
        # their hash-home shard by ``migrate_keys``.  Consulted by
        # ``_activate_keys`` so an evict→reactivate cycle returns a
        # migrated grain to its MIGRATED home, not its hash home; the
        # rebalance controller's moves would otherwise silently undo on
        # the first idle sweep.  Cleared by ``reshard`` — a mesh change
        # re-homes every key and stale pins would fight the new layout.
        self._shard_override: Dict[int, int] = {}
        # sorted (keys, shards) mirror for home_shards' vectorized
        # lookup; None = rebuild on next use (every pin mutation resets)
        self._override_sorted = None
        # per-shard free lists (LIFO): rows freed by deactivation are
        # reused in place by later activations instead of repacking the
        # block — the tensor-path analog of the reference collector's
        # non-stalling, in-place deactivation (ActivationCollector.cs:37).
        # Slots on a free list always hold init-valued state columns and
        # zeroed use clocks (reset at free time), so reuse needs no
        # per-activation scrub.
        self._free: list = [np.empty(0, dtype=np.int64)
                            for _ in range(self.n_shards)]
        # freed/high-water ratio above which a full repack still runs
        # (engine.arena_for overrides from TensorEngineConfig; <= 0 or
        # > 1 disables threshold compaction)
        self.compact_fragmentation = 0.75
        self._sorted_keys = np.empty(0, dtype=np.int64)
        self._sorted_rows = np.empty(0, dtype=np.int32)
        self._dirty = False
        self.live_count = 0
        # host-side last use: updated by host-key resolution
        self.last_use_tick = np.zeros(self.capacity, dtype=np.int64)
        # device-side last use: updated by the engine for device-routed
        # batches (injector fast path, emit hits) with a scatter-max —
        # those never cross to the host, so a host-only clock would see
        # hot rows as idle and evict live state.  Collection merges both.
        # int32 because device int64 needs jax x64 mode; the clock is a
        # tick counter, so the bound is 2**31 ticks (~25 days at 1ms/tick)
        # per engine lifetime, far beyond a process run between restarts.
        self.last_use_dev = self._dev_zeros_i32(self.capacity)

        # device-side directory mirror (int32 keys only — see device_resolve):
        # lets emit routing resolve key→row without any host round-trip,
        # which matters because d2h transfers are the slowest link.
        self._dev_sorted_keys: Optional[jnp.ndarray] = None
        self._dev_sorted_rows: Optional[jnp.ndarray] = None
        self._dev_dense: Optional[jnp.ndarray] = None
        self._dev_index_stale = True
        self._dev_dense_stale = True
        # wide-key (two-level hash/bucket) mirror — built on demand for
        # arenas whose keys exceed int32 (see device_index_wide)
        self._dev_wide: Optional[Tuple] = None
        self._dev_wide_stale = True
        # True once any activated key falls outside the int32 range:
        # narrow emits to this arena then resolve through the wide mirror
        self.has_wide_keys = False
        # hot-grain replication (the device-native StatelessWorker
        # scale-out — see promote_replicas): key → int64 row vector,
        # rows[0] = the PRIMARY (the row the directory index resolves
        # to); rows[1:] = secondary replica rows on other shards.
        # Secondary rows carry the key in ``_key_of_row`` (attribution
        # and the state columns treat them as ordinary rows) but are
        # EXCLUDED from the sorted index (``_replica_secondary``), so
        # key→row resolution stays a bijection onto primaries and the
        # delivery spread is an explicit post-resolve remap.
        self._replicas: Dict[int, np.ndarray] = {}
        self._replica_secondary = np.zeros(self.capacity, dtype=bool)
        self.replica_promotions = 0
        self.replica_demotions = 0
        self.replica_folds = 0
        # device mirror of the spread map (primary row → replica row
        # table) — rebuilt lazily, tracer-safe (device_index pattern)
        self._dev_replicas: Optional[Tuple] = None
        self._dev_replicas_stale = True
        # weakref to the owning TensorEngine (set by engine.arena_for):
        # row moves settle its auto-fusion chain first — see
        # _settle_owner_chain
        self._owner_engine: Optional[Any] = None

    def _settle_owner_chain(self) -> None:
        """Rows are about to move (growth / compaction / reshard): settle
        the owning engine's auto-fusion verification chain FIRST, while
        its pre-move state snapshot is still restorable.  This makes
        rollback-across-a-repack structurally impossible — the chain
        either verifies exact or rolls back and replays NOW, against the
        current row layout (contract: tensor/autofuse.py _settle_chain).
        Recursion-safe: a settle-triggered replay that re-enters a row
        move finds the chain already drained."""
        ref = self._owner_engine
        engine = ref() if ref is not None else None
        if engine is not None:
            fuser = getattr(engine, "autofuser", None)
            if fuser is not None and fuser._unverified:
                fuser._settle_chain()

    def _attribution(self):
        """The owning engine's workload-attribution plane when it holds
        counts for this arena — row-lifecycle events (eviction, growth,
        compaction, reshard) must keep its per-row traffic column in
        step with the key→row map (tensor/attribution.py)."""
        ref = self._owner_engine
        engine = ref() if ref is not None else None
        att = getattr(engine, "attribution", None) \
            if engine is not None else None
        return att if att is not None and att.has_state(self.info.name) \
            else None

    def _stream_routes(self):
        """The owning engine's stream-subscription routes whose
        SUBSCRIBER arena is this one (tensor/streams_plane.py) — the
        deactivation path retires victims from the adjacency BEFORE
        their rows return to the free list, so a reused slot can never
        receive a dead subscription's events."""
        ref = self._owner_engine
        engine = ref() if ref is not None else None
        if engine is None:
            return ()
        return [r for r in getattr(engine, "_stream_routes", {}).values()
                if r.type_name == self.info.name]

    # -- state columns ------------------------------------------------------

    def _make_column(self, f: StateField, capacity: int) -> jnp.ndarray:
        col = jnp.full((capacity, *f.shape), f.init, dtype=f.dtype)
        if self.sharding is not None:
            col = jax.device_put(col, self.sharding)
        return col

    def _dev_zeros_i32(self, capacity: int) -> jnp.ndarray:
        z = jnp.zeros(capacity, dtype=jnp.int32)
        if self.sharding is not None:
            z = jax.device_put(z, self.sharding)
        return z

    def touch_rows_dev(self, rows: jnp.ndarray, tick: int) -> None:
        """Record device-routed traffic for collection (scatter-max, stays
        on device; padding rows -1 dropped)."""
        self.last_use_dev = _touch_kernel(self.last_use_dev, rows,
                                          jnp.int32(tick))

    def touch_rows_dense(self, segments: jnp.ndarray, tick: int) -> None:
        """Pull-mode delivery touch (tensor/streams_plane.py): the
        row-aligned offsets already know which rows received — one
        elementwise max instead of an edge-sized scatter."""
        self.last_use_dev = _touch_dense_kernel(self.last_use_dev,
                                                segments, jnp.int32(tick))

    def effective_last_use(self) -> np.ndarray:
        """Merge the host and device use clocks (collection-time only)."""
        return np.maximum(self.last_use_tick, np.asarray(
            host_read("arena", self.last_use_dev), dtype=np.int64))

    def _init_state_columns(self, capacity: int) -> None:
        self.state = {name: self._make_column(f, capacity)
                      for name, f in self.info.state_fields.items()}

    def adopt_state(self, new_state: Dict[str, Any]) -> None:
        """Flip the live columns to a program's output buffers — the
        double-buffer handoff of donated execution (the engine's step
        and fused-window programs take the current columns as DONATED
        inputs; their outputs become the live state).  Validates the
        pytree layout cheaply (host-side shape/dtype attributes only):
        a donated program must never smuggle in a wrong-shaped column,
        because every cached row vector and directory mirror assumes
        the capacity."""
        if new_state is self.state:
            return
        for name, col in self.state.items():
            new = new_state.get(name)
            if new is None:
                raise ValueError(
                    f"adopt_state({self.info.name}): program output "
                    f"dropped column {name!r}")
            if tuple(new.shape) != tuple(col.shape) \
                    or new.dtype != col.dtype:
                raise ValueError(
                    f"adopt_state({self.info.name}.{name}): output "
                    f"{new.shape}/{new.dtype} != live "
                    f"{col.shape}/{col.dtype}")
        self.state = new_state
        self.state_flips += 1

    # -- key → row resolution ----------------------------------------------

    def _rebuild_index(self) -> None:
        # replica SECONDARIES are excluded: the index stays a bijection
        # key → primary row; delivery fans across replicas through the
        # explicit spread remap (spread_rows_host / replica_mirror)
        live = self._key_of_row >= 0
        if self._replicas:
            live = live & ~self._replica_secondary
        rows = np.nonzero(live)[0].astype(np.int32)
        keys = self._key_of_row[rows]
        order = np.argsort(keys, kind="stable")
        self._sorted_keys = keys[order]
        self._sorted_rows = rows[order]
        self._dirty = False
        self._dev_index_stale = True
        self._dev_dense_stale = True
        self._dev_wide_stale = True

    # -- device-side directory mirror ---------------------------------------

    def device_index(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """The key→row map as device arrays (sorted int32 keys + rows).

        This is the 'directory == sharding map' realization: the same
        partition the host serves to the control plane is resident on the
        mesh, so batched routing (emits, injections) resolves destinations
        with a vectorized searchsorted instead of a host hop.  Keys wider
        than int32 fall back to the host path (hashed/string grain keys are
        rare on the hot path; int-keyed grains cover the benchmarks)."""
        if self._dirty:
            self._rebuild_index()
        if self._dev_index_stale or self._dev_sorted_keys is None:
            keys32 = self._sorted_keys.astype(np.int32)
            if np.any(keys32.astype(np.int64) != self._sorted_keys):
                raise OverflowError(
                    f"arena {self.info.name}: keys exceed int32; device "
                    f"routing unavailable (use host-side resolution)")
            # pad to capacity with the sentinel so the resolve kernel's
            # shapes only change on capacity growth (not per activation)
            pad = self.capacity - len(keys32)
            keys_padded = np.concatenate(
                [keys32, np.full(pad, 2**31 - 1, np.int32)])
            rows_padded = np.concatenate(
                [self._sorted_rows, np.full(pad, -1, np.int32)])
            dk = jnp.asarray(keys_padded)
            dr = jnp.asarray(rows_padded)
            if self.sharding is not None:
                # replicate the index: every shard routes locally
                from jax.sharding import NamedSharding, PartitionSpec
                repl = NamedSharding(self.sharding.mesh, PartitionSpec())
                dk = jax.device_put(dk, repl)
                dr = jax.device_put(dr, repl)
            if isinstance(dk, jax.core.Tracer):
                # called under an abstract trace (e.g. the fused-tick
                # discovery pass): the values are trace-local — caching
                # them would leak tracers into later real calls
                return dk, dr
            self._dev_sorted_keys = dk
            self._dev_sorted_rows = dr
            self._dev_index_stale = False
        return self._dev_sorted_keys, self._dev_sorted_rows

    # dense direct-map mirror: for SMALL integer key spaces the directory
    # collapses further, from a binary search to one gather — measured
    # ~80ms/tick of searchsorted at 1M messages becomes ~1ms.  Worth 4
    # bytes per key-space slot while max_key stays within the bound.
    DENSE_KEY_BOUND = 1 << 23  # 8M slots = 32MB ceiling

    def dense_index(self):
        """key→row as a dense device array (or None when the key space is
        too wide/sparse to afford it).  rows[key] == -1 for unseen keys."""
        if self._dirty:
            self._rebuild_index()
        if len(self._sorted_keys) == 0:
            return None
        max_key = int(self._sorted_keys[-1])
        if int(self._sorted_keys[0]) < 0 or max_key >= self.DENSE_KEY_BOUND:
            return None
        size = max_key + 1
        # sparsity guard: a handful of grains with one huge key must not
        # buy a multi-MB rebuild per activation — dense only pays when the
        # key space is reasonably occupied (or trivially small)
        if size > max(4 * max(1, self.live_count), 65536):
            return None
        if not self._dev_dense_stale and self._dev_dense is not None \
                and self._dev_dense.shape[0] >= size:
            return self._dev_dense
        # pad to the next power of two so growth re-traces rarely
        alloc = 1 << (size - 1).bit_length()
        dense = np.full(alloc, -1, dtype=np.int32)
        dense[self._sorted_keys] = self._sorted_rows
        dd = jnp.asarray(dense)
        if self.sharding is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            dd = jax.device_put(
                dd, NamedSharding(self.sharding.mesh, PartitionSpec()))
        if isinstance(dd, jax.core.Tracer):
            return dd  # trace-local (see device_index)
        self._dev_dense = dd
        self._dev_dense_stale = False
        return dd

    def device_index_wide(self) -> Tuple[jnp.ndarray, jnp.ndarray,
                                         jnp.ndarray, jnp.ndarray]:
        """Wide-key directory mirror: ``(sorted_h, rows_by_h, hi_col,
        lo_col)`` device arrays.  Destination resolution searchsorts the
        30-bit bucket hashes, then verifies candidates against the full
        key words per row — two gathers and one compare beyond the
        narrow path, keeping 64-bit/hashed/string-keyed grains on the
        device hot path (reference key breadth: UniqueKey.cs:34)."""
        if self._dirty:
            self._rebuild_index()
        if self._dev_wide_stale or self._dev_wide is None:
            hi, lo = split_wide_keys(self._sorted_keys)
            h = mix32_np(hi, lo)
            order = np.argsort(h, kind="stable")
            pad = self.capacity - len(h)
            sorted_h = np.concatenate(
                [h[order], np.full(pad, 2**31 - 1, np.int32)])
            rows_by_h = np.concatenate(
                [self._sorted_rows[order], np.full(pad, -1, np.int32)])
            hi_col = np.zeros(self.capacity, np.int32)
            lo_col = np.full(self.capacity, -1, np.int32)
            hi_col[self._sorted_rows] = hi
            lo_col[self._sorted_rows] = lo
            parts = tuple(jnp.asarray(p) for p in
                          (sorted_h, rows_by_h, hi_col, lo_col))
            if self.sharding is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                repl = NamedSharding(self.sharding.mesh, PartitionSpec())
                parts = tuple(jax.device_put(p, repl) for p in parts)
            if isinstance(parts[0], jax.core.Tracer):
                return parts  # trace-local (see device_index)
            self._dev_wide = parts
            self._dev_wide_stale = False
        return self._dev_wide

    def lookup_rows(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized lookup; returns (rows int32, found bool)."""
        if self._dirty:
            self._rebuild_index()
        if len(self._sorted_keys) == 0:
            return (np.full(len(keys), -1, np.int32),
                    np.zeros(len(keys), bool))
        idx = np.searchsorted(self._sorted_keys, keys)
        idx = np.minimum(idx, len(self._sorted_keys) - 1)
        found = self._sorted_keys[idx] == keys
        rows = np.where(found, self._sorted_rows[idx], -1).astype(np.int32)
        return rows, found

    def resolve_rows(self, keys: np.ndarray, auto_activate: bool = True,
                     tick: int = 0) -> np.ndarray:
        """key→row with auto-activation of unseen keys
        (batched GetOrCreateActivation)."""
        keys = np.asarray(keys, dtype=np.int64)
        rows, found = self.lookup_rows(keys)
        if auto_activate and not found.all():
            missing = np.unique(keys[~found])
            self._activate_keys(missing)
            rows, found = self.lookup_rows(keys)
            if not found.all():
                raise ArenaFullError(
                    f"arena {self.info.name}: activation failed for "
                    f"{(~found).sum()} keys")
        self.last_use_tick[rows[rows >= 0]] = tick
        return rows

    def home_shards(self, keys: np.ndarray) -> np.ndarray:
        """Which shard block each key activates in: the stable hash,
        overridden per key by any live-migration pin.  The override
        lookup is one vectorized searchsorted over a sorted mirror of
        the (small) pinned set, cached until the pins mutate — this
        sits on the hot activation path, so a long-lived pin set must
        not pay a rebuild per batch; the unpinned common case pays a
        truthiness check."""
        shards = shard_of_keys(keys, self.n_shards)
        if self._shard_override:
            if self._override_sorted is None:
                ok = np.fromiter(self._shard_override.keys(),
                                 dtype=np.int64,
                                 count=len(self._shard_override))
                ov = np.fromiter(self._shard_override.values(),
                                 dtype=np.int64,
                                 count=len(self._shard_override))
                order = np.argsort(ok)
                self._override_sorted = (ok[order], ov[order])
            ok, ov = self._override_sorted
            idx = np.minimum(np.searchsorted(ok, keys), len(ok) - 1)
            hit = ok[idx] == keys
            shards[hit] = ov[idx[hit]]
        return shards

    def _take_rows(self, shards: np.ndarray) -> np.ndarray:
        """Allocate one slot per entry of ``shards`` (free-list LIFO
        reuse first — most-recently-freed slots are the likeliest still
        resident in device cache — then the bump pointer) WITHOUT
        binding keys: the allocation half of ``_activate_keys``, shared
        with ``migrate_keys`` (which must copy state into the slots
        before the key map flips).  Callers guarantee capacity."""
        rows = np.empty(len(shards), dtype=np.int64)
        for s in np.unique(shards):
            sel = np.nonzero(shards == s)[0]
            parts = []
            reuse = min(len(sel), len(self._free[s]))
            if reuse:
                parts.append(self._free[s][-reuse:])
                self._free[s] = self._free[s][:-reuse]
            fresh = len(sel) - reuse
            if fresh:
                start = int(self._shard_next[s])
                base = s * self.shard_capacity
                parts.append(np.arange(start, start + fresh) + base)
                self._shard_next[s] += fresh
            rows[sel] = np.concatenate(parts) if len(parts) > 1 \
                else parts[0]
        return rows

    def _ensure_capacity(self, need_per_shard: np.ndarray) -> None:
        """Grow until every shard block can absorb ``need_per_shard``
        more rows.  Free-list slots count as available — freed rows are
        reused in place before the bump pointer advances, so steady
        churn (activate/evict cycles) never grows the arena."""
        free_counts = np.array([len(f) for f in self._free],
                               dtype=np.int64)
        while np.any(self._shard_next
                     + np.maximum(need_per_shard - free_counts, 0)
                     > self.shard_capacity):
            self._grow()  # remaps the free lists; free_counts unchanged

    def _activate_keys(self, keys: np.ndarray) -> None:
        if len(keys) and int(keys.min()) < 0:
            # the row map's free-slot sentinel is -1: the grain key
            # domain is [0, 2**63) — hash wider identities into it
            # (GrainId string/guid keys already do)
            raise ValueError(
                f"arena {self.info.name}: grain keys must be in "
                f"[0, 2**63); got {int(keys.min())}")
        if len(keys) and int(keys.max()) >= 2**31 - 1:
            self.has_wide_keys = True
        shards = self.home_shards(keys)
        self._ensure_capacity(np.bincount(shards,
                                          minlength=self.n_shards))
        rows = self._take_rows(shards)
        self._key_of_row[rows] = keys
        self.live_count += len(keys)
        self._dirty = True
        if self.store is not None:
            self._load_persisted(keys)

    def _load_persisted(self, keys: np.ndarray) -> None:
        """Activation stage 2, batched: scatter persisted rows (previously
        evicted or checkpointed) into the freshly allocated slots
        (reference: Catalog.SetupActivationState :731)."""
        stored = self.store.read_many(self.info.name, keys.tolist())
        if not stored:
            return
        found = np.array(sorted(stored), dtype=np.int64)
        rows, ok = self.lookup_rows(found)
        assert ok.all()
        dst = jnp.asarray(rows, dtype=jnp.int32)
        for name, f in self.info.state_fields.items():
            vals = np.stack([np.asarray(stored[int(k)][name], dtype=f.dtype)
                             for k in found])
            self.state[name] = self.state[name].at[dst].set(
                jnp.asarray(vals))
        self.restored_count += len(found)

    # -- growth -------------------------------------------------------------

    def _grow(self) -> None:
        """Double the per-shard block size, repacking rows so each shard's
        block stays contiguous (rows move; the key index is rebuilt —
        resharding is the same op at a bigger granularity)."""
        self._settle_owner_chain()
        old_per = self.shard_capacity
        new_per = old_per * 2
        new_capacity = new_per * self.n_shards
        old_rows = np.nonzero(self._key_of_row >= 0)[0]
        old_shards = old_rows // old_per
        new_rows = (old_shards * new_per) + (old_rows % old_per)

        new_key_of_row = np.full(new_capacity, -1, dtype=np.int64)
        new_key_of_row[new_rows] = self._key_of_row[old_rows]
        new_last_use = np.zeros(new_capacity, dtype=np.int64)
        new_last_use[new_rows] = self.last_use_tick[old_rows]

        new_state: Dict[str, jnp.ndarray] = {}
        idx = jnp.asarray(old_rows, dtype=jnp.int32)
        dst = jnp.asarray(new_rows, dtype=jnp.int32)
        for name, f in self.info.state_fields.items():
            col = self._make_column(f, new_capacity)
            col = col.at[dst].set(self.state[name][idx])
            new_state[name] = col
        self.last_use_dev = self._dev_zeros_i32(new_capacity).at[dst].set(
            self.last_use_dev[idx])
        att = self._attribution()
        if att is not None:
            # traffic counts move with their rows (device scatter, the
            # last_use_dev discipline — keys keep their totals)
            att.remap_rows(self, old_rows, new_rows, new_capacity)
        # replica groups ride the same block-preserving row map
        if self._replicas:
            self._replicas = {
                k: (r // old_per) * new_per + (r % old_per)
                for k, r in self._replicas.items()}
        new_sec = np.zeros(new_capacity, dtype=bool)
        new_sec[new_rows] = self._replica_secondary[old_rows]
        self._replica_secondary = new_sec
        self._dev_replicas_stale = True

        self.state = new_state
        self.shard_capacity = new_per
        self.capacity = new_capacity
        self._key_of_row = new_key_of_row
        self.last_use_tick = new_last_use
        # free slots ride along: row s*old_per + off → s*new_per + off
        # (the fresh columns are init-valued everywhere non-live, so the
        # remapped slots keep the clean-on-free invariant)
        self._free = [s * new_per + (f - s * old_per)
                      for s, f in enumerate(self._free)]
        self._dirty = True
        self.generation += 1

    def reserve(self, n: int) -> None:
        """Pre-size so ~n activations fit without growth mid-benchmark."""
        per_shard_target = -(-n // self.n_shards)
        while self.shard_capacity < per_shard_target * 2:
            self._grow()

    # -- collection (reference: ActivationCollector.cs:37) -------------------

    def rows_to_host(self, rows: np.ndarray) -> Dict[str, np.ndarray]:
        """Gather the given rows' state columns to host.  All gathers
        dispatch first, then ONE ``jax.device_get`` fetches the whole
        tree — the per-field d2h round-trips (each paying a completion
        observation) collapse into one.  Gathers
        are pow2-padded (row 0 repeated, sliced off after the fetch) so
        data-dependent row counts reuse O(log n) compiled gathers."""
        n = len(rows)
        idx = jnp.asarray(_pow2_pad(rows, 0))
        host = host_read("arena", {name: col[idx]
                                   for name, col in self.state.items()})
        return {name: col[:n] for name, col in host.items()}

    def shard_occupancy(self) -> np.ndarray:
        """Live rows per shard block (int64[n_shards]) — the balance
        gauge behind ``arena.shard_occupancy`` and the multichip bench's
        per-shard balance section.  Host-only arithmetic."""
        live = np.nonzero(self._key_of_row >= 0)[0]
        return np.bincount(live // self.shard_capacity,
                           minlength=self.n_shards).astype(np.int64)

    def fragmentation(self) -> float:
        """Worst per-shard freed/high-water ratio (0.0 = no holes).  The
        threshold trigger for full compaction — with in-place free-list
        reuse fragmentation is a capacity-reclaim concern, not a
        correctness one."""
        hw = np.maximum(self._shard_next, 1).astype(np.float64)
        free = np.array([len(f) for f in self._free], dtype=np.float64)
        return float((free / hw).max()) if self.n_shards else 0.0

    def select_idle_rows(self, older_than_tick: int) -> np.ndarray:
        """Victim selection for collection: one vectorized compare over
        the merged use clocks ON DEVICE (reference bucket test:
        ActivationCollector.cs:37); only the boolean victim mask crosses
        to the host.  Returns victim row ids (host int64)."""
        # settle BEFORE computing victims: a settle-triggered replay may
        # grow/repack this arena, which would invalidate victim row ids
        self._settle_owner_chain()
        live = self._key_of_row >= 0
        if not live.any():
            return np.empty(0, dtype=np.int64)
        cutoff = int(np.clip(older_than_tick, -2**31 + 1, 2**31 - 1))
        host_clock = np.clip(self.last_use_tick, 0, 2**31 - 1) \
            .astype(np.int32)
        mask = _idle_mask_kernel(self.last_use_dev,
                                 jnp.asarray(host_clock),
                                 jnp.asarray(live), jnp.int32(cutoff))
        return np.flatnonzero(np.asarray(mask)).astype(np.int64)

    def deactivate_idle_rows(self, rows: np.ndarray, older_than_tick: int,
                             write_back: bool = True) -> int:
        """Deactivate the subset of ``rows`` still live and still idle —
        the re-validated chunk step of incremental collection.  Rows
        touched (either clock) since their sweep selected them are
        spared; rows re-used by a different key stay eligible only if
        that key is itself idle past the cutoff (evicting an idle row is
        always permitted)."""
        # settle first: a settle-triggered replay may grow/repack this
        # arena, and the liveness/idleness re-validation below must run
        # against the post-settle layout
        self._settle_owner_chain()
        rows = np.asarray(rows, dtype=np.int64)
        rows = rows[(rows >= 0) & (rows < self.capacity)]
        rows = rows[self._key_of_row[rows] >= 0]
        if len(rows) == 0:
            return 0
        dev = np.asarray(host_read("arena", self.last_use_dev[
            jnp.asarray(_pow2_pad(rows, 0))]))[:len(rows)]
        idle = np.maximum(self.last_use_tick[rows],
                          dev.astype(np.int64)) < older_than_tick
        return self._deactivate_rows(rows[idle], write_back)

    def collect(self, older_than_tick: int, write_back: bool = True) -> int:
        """Deactivate rows idle since before ``older_than_tick`` — the
        tensor-path activation collector: the reference buckets
        activations by last-use quantum and deactivates whole buckets
        (reference: ActivationCollector.cs:37, age-based
        DeactivateActivations Catalog.cs:836); here the bucket test is one
        vectorized compare over the merged use clocks.  Freed rows return
        to the per-shard free lists in place — no repack, generation
        preserved (full compaction only past ``compact_fragmentation``).

        With a store and ``write_back``, victim rows are written through
        the storage bridge first, so a later message to an evicted grain
        re-activates it with its state (the deactivate→storage→reactivate
        cycle of the reference).  Returns the number of rows evicted."""
        return self._deactivate_rows(
            self.select_idle_rows(older_than_tick), write_back)

    def evict_keys(self, keys: np.ndarray, write_back: bool = True) -> int:
        """Deactivate specific keys (write-back first when a store is
        attached) — the arena half of directory handoff on ring change:
        rows this silo no longer owns leave through storage and the new
        owner re-activates them on first touch (reference:
        GrainDirectoryHandoffManager.cs:141; deactivate→storage→
        reactivate cycle, Catalog.cs:836)."""
        self._settle_owner_chain()
        keys = np.asarray(keys, dtype=np.int64)
        if self._replicas:
            # a replicated key folds back to one row FIRST, so the
            # write-back below stores the merged state and the
            # secondaries' slots free through the demotion path
            for k in keys.tolist():
                if int(k) in self._replicas:
                    self.demote_replicas(int(k))
        rows, found = self.lookup_rows(keys)
        return self._deactivate_rows(rows[found], write_back)

    def _deactivate_rows(self, victims: np.ndarray, write_back: bool) -> int:
        """Shared deactivation tail (collect + evict_keys +
        deactivate_idle_rows): write-back FIRST — victims are freed only
        after the store acks, so an injected storage fault mid-chunk
        leaves them live for the retry — then return the slots to the
        per-shard free lists in place.  Rows do not move: the generation
        is preserved (cached resolved rows over SURVIVING keys stay
        valid, no re-resolution/recompile storm) and only
        ``eviction_epoch`` bumps so caches re-check liveness cheaply.
        Full compaction runs only past the fragmentation threshold."""
        # NOTE: callers settle the owner chain BEFORE computing victims
        # (select_idle_rows / evict_keys / deactivate_idle_rows) — a
        # settle here would be too late: its replay could repack the
        # arena and stale the victim row ids already in hand
        victims = np.asarray(victims, dtype=np.int64)
        if self._replicas:
            # replica member rows never collect individually — demotion
            # is the only exit (evict_keys demotes first, then re-enters)
            victims = victims[~self._replica_member_mask(victims)]
        if len(victims) == 0:
            return 0
        keys = self._key_of_row[victims]
        att = self._attribution()
        if att is not None:
            # retire the victims' traffic counts per key BEFORE the rows
            # return to the free list — a reused slot must never inherit
            # the evicted grain's attribution (epoch bit-exactness)
            att.on_evict(self, victims, keys)
        for route in self._stream_routes():
            # retire evicted subscribers from the device adjacency
            # BEFORE slot reuse is possible (tensor/streams_plane.py:
            # a subscribed victim dirties the row layout; otherwise the
            # stamp just advances and no rebuild is paid)
            route.on_evict(self, victims, keys)
        if write_back and self.store is not None:
            # columnar fast path: the gathered columns go to the store
            # as-is — no O(victims) list-of-dicts construction here
            self.store.write_many_columnar(
                self.info.name, keys.tolist(), self.rows_to_host(victims))
        self._key_of_row[victims] = -1
        self.live_count -= len(victims)
        self.evicted_count += len(victims)
        self._free_rows(victims)
        self.eviction_epoch += 1
        self._dirty = True
        if 0.0 < self.compact_fragmentation <= 1.0 \
                and self.fragmentation() > self.compact_fragmentation:
            self._compact()
        return len(victims)

    def _free_rows(self, victims: np.ndarray) -> None:
        """Return freed slots to their shard's free list and scrub them:
        state columns back to field inits (a reused slot must never leak
        the evicted grain's state; restore-from-store happens at
        activation), both use clocks zeroed."""
        shards = victims // self.shard_capacity
        order = np.argsort(shards, kind="stable")
        victims = victims[order]
        bounds = np.searchsorted(shards[order], np.arange(self.n_shards + 1))
        for s in range(self.n_shards):
            part = victims[bounds[s]:bounds[s + 1]]
            if len(part):
                self._free[s] = np.concatenate([self._free[s], part])
        # out-of-range fill + mode="drop": the padding lanes scatter
        # nowhere
        idx = jnp.asarray(_pow2_pad(victims, self.capacity))
        for name, f in self.info.state_fields.items():
            self.state[name] = self.state[name].at[idx].set(
                jnp.full(f.shape, f.init, dtype=f.dtype), mode="drop")
        self.last_use_dev = self.last_use_dev.at[idx].set(0, mode="drop")
        self.last_use_tick[victims] = 0

    def _compact(self) -> None:
        """Repack each shard block so live rows are contiguous from the
        block base (free lists clear; the bump pointer resets to the live
        count).  Rows move → generation bump; holders re-resolve.  Runs
        on explicit call or when fragmentation crosses the threshold —
        never on the ordinary deactivation path."""
        old_rows = np.nonzero(self._key_of_row >= 0)[0]
        shards = old_rows // self.shard_capacity
        # vectorized per-shard repack: old_rows is ascending, so each
        # shard's members are contiguous — their rank within the shard is
        # the global index minus the shard's cumulative start
        next_free = np.bincount(shards, minlength=self.n_shards) \
            .astype(np.int64)
        starts = np.concatenate(([0], np.cumsum(next_free)[:-1]))
        new_rows = (shards * self.shard_capacity
                    + np.arange(len(old_rows)) - starts[shards])

        keys = self._key_of_row[old_rows]
        last_use = self.last_use_tick[old_rows]
        self._key_of_row.fill(-1)
        self._key_of_row[new_rows] = keys
        self.last_use_tick.fill(0)
        self.last_use_tick[new_rows] = last_use
        self._shard_next = next_free
        self._free = [np.empty(0, dtype=np.int64)
                      for _ in range(self.n_shards)]

        idx = jnp.asarray(old_rows, dtype=jnp.int32)
        dst = jnp.asarray(new_rows, dtype=jnp.int32)
        for name, f in self.info.state_fields.items():
            col = self._make_column(f, self.capacity)
            self.state[name] = col.at[dst].set(self.state[name][idx])
        self.last_use_dev = self._dev_zeros_i32(self.capacity).at[dst].set(
            self.last_use_dev[idx])
        att = self._attribution()
        if att is not None:
            att.remap_rows(self, old_rows, new_rows, self.capacity)
        if self._replicas:
            remap = np.full(self.capacity, -1, dtype=np.int64)
            remap[old_rows] = new_rows
            self._replicas = {k: remap[r]
                              for k, r in self._replicas.items()}
            new_sec = np.zeros(self.capacity, dtype=bool)
            new_sec[new_rows] = self._replica_secondary[old_rows]
            self._replica_secondary = new_sec
            self._dev_replicas_stale = True
        self._dirty = True
        self.generation += 1

    # -- live migration (batched deactivate-with-state-handoff) --------------

    def migrate_keys(self, keys: np.ndarray, dst_shards,
                     pin: bool = True) -> int:
        """Batched LIVE MIGRATION: move k grains into explicit
        destination shard blocks as ONE columnar device gather/scatter
        per state column — never per-grain Python.  Semantically an
        atomic deactivate-with-state-handoff → reactivate on the target
        shard: the freed slots return to their shard free lists
        scrubbed (the clean-on-free invariant), the eviction epoch
        bumps — in-flight batches holding pre-move rows re-validate
        their stamps and re-deliver through the existing miss machinery,
        so single-activation holds throughout (a key is never resident
        in two rows; the map flips old→new in one host step) — and
        attribution retires the movers' counts per KEY (the eviction
        discipline: totals survive the move, a reused slot never
        inherits them).  ``pin`` records the move in the shard-override
        map so an evict→reactivate cycle returns the grain to its
        migrated home.  Generation is PRESERVED: surviving rows stay
        put, so resolved-row caches over unmigrated keys stay valid.
        Returns grains actually moved."""
        self._settle_owner_chain()
        keys = np.asarray(keys, dtype=np.int64)
        dst = np.broadcast_to(np.asarray(dst_shards, dtype=np.int64),
                              keys.shape).copy()
        keys, first = np.unique(keys, return_index=True)
        dst = dst[first]  # duplicate keys: first destination wins
        if len(keys) and (int(dst.min()) < 0
                          or int(dst.max()) >= self.n_shards):
            raise ValueError(
                f"arena {self.info.name}: migration destination shard "
                f"out of range [0, {self.n_shards})")
        rows, found = self.lookup_rows(keys)
        cur = rows.astype(np.int64) // self.shard_capacity
        sel = found & (dst != cur)
        if self._replicas:
            # a replicated grain already spans shards — moving its
            # primary would not change its load picture, and the replica
            # row table would go stale.  Demote first to migrate.
            sel &= ~np.isin(keys, np.fromiter(
                self._replicas, np.int64, len(self._replicas)))
        keys, dst = keys[sel], dst[sel]
        if len(keys) == 0:
            return 0
        # capacity FIRST: _grow moves rows, so the source rows resolve
        # after any growth (destination demand counted conservatively —
        # the movers' own slots free only after the copy)
        self._ensure_capacity(np.bincount(dst, minlength=self.n_shards))
        src_rows, found = self.lookup_rows(keys)
        assert found.all()
        src_rows = src_rows.astype(np.int64)
        att = self._attribution()
        if att is not None:
            # retire the movers' traffic per key BEFORE the move (the
            # on_evict discipline): counts follow the KEY through the
            # retired mirror, and the freed slot restarts at zero
            att.on_evict(self, src_rows, keys)
        for route in self._stream_routes():
            # subscriptions SURVIVE a migration (unlike eviction) — the
            # route only needs its row-addressed pull layout rebuilt
            route.on_migrate(self, keys)
        new_rows = self._take_rows(dst)
        # the columnar move: one compiled gather+scatter per column.
        # Source pads with row 0 (harmlessly gathered), destination
        # pads with capacity (mode="drop" discards those lanes); both
        # pad to the same pow2 so the compile set stays O(log n).
        src_idx = jnp.asarray(_pow2_pad(src_rows, 0))
        dst_idx = jnp.asarray(_pow2_pad(new_rows, self.capacity))
        for name in self.info.state_fields:
            col = self.state[name]
            self.state[name] = col.at[dst_idx].set(col[src_idx],
                                                   mode="drop")
        self.last_use_dev = self.last_use_dev.at[dst_idx].set(
            self.last_use_dev[src_idx], mode="drop")
        # host identity flips in one step: new rows bind, old rows free
        self.last_use_tick[new_rows] = self.last_use_tick[src_rows]
        self._key_of_row[new_rows] = keys
        self._key_of_row[src_rows] = -1
        self._free_rows(src_rows)
        home = shard_of_keys(keys, self.n_shards)
        for k, d, h in zip(keys.tolist(), dst.tolist(), home.tolist()):
            if pin and d != h:
                self._shard_override[k] = d
            else:
                # moved back to (or landing on) its hash home: drop the
                # pin — reactivation falls through to the stable hash
                self._shard_override.pop(k, None)
        self._override_sorted = None
        self.migrated_count += len(keys)
        self.eviction_epoch += 1
        self._dirty = True
        return len(keys)

    # -- hot-grain replication (break the single-hot-grain ceiling) ----------
    # A grain whose traffic exceeds what one shard can absorb — and whose
    # state folds commutatively (StateField.fold) — promotes to k replica
    # rows spread over shards.  Delivery scatters lanes across the
    # replicas (lane hash), so the per-pair exchange demand divides by k;
    # reads/checkpoints fold the replicas back with one reduction.  The
    # key→row bijection is preserved: lookups resolve to the PRIMARY
    # (``_rebuild_index`` excludes secondaries) and only the spread step
    # re-points delivery lanes.

    REPLICA_TABLE_WIDTH = 8  # mirror row width; max_replicas knob ≤ this

    def _replica_mirror_host(self) -> Tuple[np.ndarray, np.ndarray,
                                            np.ndarray]:
        """(prim, counts, table) host arrays — the one construction both
        the device mirror and the host spread twin derive from, so the
        two resolutions agree bit-exactly."""
        items = sorted(self._replicas.items(), key=lambda kv: int(kv[1][0]))
        alloc = 1 << max(0, len(items) - 1).bit_length()
        kmax = self.REPLICA_TABLE_WIDTH
        prim = np.full(alloc, 2**31 - 1, dtype=np.int32)
        counts = np.ones(alloc, dtype=np.int32)
        table = np.full((alloc, kmax), -1, dtype=np.int32)
        for i, (_, rws) in enumerate(items):
            k = min(len(rws), kmax)
            prim[i] = int(rws[0])
            counts[i] = k
            table[i, :k] = rws[:k]
        return prim, counts, table

    def replica_mirror(self) -> Tuple[jnp.ndarray, jnp.ndarray,
                                      jnp.ndarray]:
        """Device mirror of the replica table for
        ``_spread_replicas_kernel`` — row-keyed (works regardless of key
        width), replicated across the mesh, cached until a
        promote/demote or row move stales it."""
        if not self._dev_replicas_stale and self._dev_replicas is not None:
            return self._dev_replicas
        parts = tuple(jnp.asarray(a) for a in self._replica_mirror_host())
        if self.sharding is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            repl = NamedSharding(self.sharding.mesh, PartitionSpec())
            parts = tuple(jax.device_put(a, repl) for a in parts)
        if isinstance(parts[0], jax.core.Tracer):
            return parts  # trace-local (see device_index)
        self._dev_replicas = parts
        self._dev_replicas_stale = False
        return parts

    def spread_rows_host(self, rows: np.ndarray) -> np.ndarray:
        """Host twin of the spread kernel: identical lane-hash replica
        choice, applied to host-resolved rows (injector refresh, host
        resolve path, fused prepare)."""
        rows = np.asarray(rows)
        if not self._replicas or len(rows) == 0:
            return rows
        prim, counts, table = self._replica_mirror_host()
        r = rows.astype(np.int64)
        idx = np.clip(np.searchsorted(prim, r), 0, len(prim) - 1)
        hit = (prim[idx].astype(np.int64) == r) & (r >= 0)
        lanes = np.arange(len(r), dtype=np.uint32)
        h = (lanes * np.uint32(2654435761)) >> np.uint32(8)
        choice = (h % counts[idx].astype(np.uint32)).astype(np.int64)
        alt = table[idx, choice].astype(np.int64)
        out = np.where(hit & (alt >= 0), alt, r)
        return out.astype(rows.dtype)

    def _replica_member_mask(self, rows: np.ndarray) -> np.ndarray:
        """True for rows inside any replica group (primary or secondary)
        — those rows never collect/evict individually; demotion is the
        only exit."""
        rows = np.asarray(rows, dtype=np.int64)
        mask = self._replica_secondary[rows].copy()
        if self._replicas:
            prim = np.fromiter((int(r[0]) for r in self._replicas.values()),
                               np.int64, len(self._replicas))
            mask |= np.isin(rows, prim)
        return mask

    def promote_replicas(self, key: int, k: int) -> int:
        """Promote ``key`` to ``k`` replica rows (its existing row stays
        the primary; k-1 fresh secondaries land on OTHER shards,
        round-robin).  Secondary slots come off the free lists holding
        field inits — the fold identity — so a fresh replica contributes
        nothing to the merge.  Generation bumps (the next durable
        checkpoint is a full; deltas never span a replication change)
        and the eviction epoch bumps (in-flight resolved rows
        re-validate).  Returns the group size actually installed."""
        k = int(max(2, min(k, self.REPLICA_TABLE_WIDTH)))
        self._settle_owner_chain()
        key = int(key)
        if key in self._replicas:
            return len(self._replicas[key])
        rows, found = self.lookup_rows(np.array([key], dtype=np.int64))
        if not found[0]:
            raise KeyError(
                f"arena {self.info.name}: cannot replicate key {key} — "
                f"not live")
        prim_shard = int(rows[0]) // self.shard_capacity
        if self.n_shards > 1:
            others = [s for s in range(self.n_shards) if s != prim_shard]
            shards = np.array([others[i % len(others)]
                               for i in range(k - 1)], dtype=np.int64)
        else:
            shards = np.zeros(k - 1, dtype=np.int64)
        self._ensure_capacity(np.bincount(shards,
                                          minlength=self.n_shards))
        # re-lookup AFTER the capacity check: _grow moves rows
        prow, found = self.lookup_rows(np.array([key], dtype=np.int64))
        assert found[0]
        prow = int(prow[0])
        sec = self._take_rows(shards)
        self._key_of_row[sec] = key
        self._replica_secondary[sec] = True
        self._replicas[key] = np.concatenate(
            [np.array([prow], dtype=np.int64), sec])
        self.last_use_tick[sec] = self.last_use_tick[prow]
        self.replica_promotions += 1
        self._dirty = True
        self._dev_replicas_stale = True
        self.generation += 1
        self.eviction_epoch += 1
        return k

    def _fold_replica_host(self, rws: np.ndarray) -> Dict[str, np.ndarray]:
        """Commutative merge of one replica group's rows on host.
        fold="sum" merges as Σ replicas − (k−1)·init (bit-exact for
        integer dtypes — the exactness-oracle contract); "max"/"min"
        reduce directly (their identity IS the init by declaration)."""
        rws = np.asarray(rws, dtype=np.int64)
        host = self.rows_to_host(rws)
        k = len(rws)
        out: Dict[str, np.ndarray] = {}
        for name, f in self.info.state_fields.items():
            vals = host[name]
            if f.fold == "max":
                out[name] = vals.max(axis=0)
            elif f.fold == "min":
                out[name] = vals.min(axis=0)
            else:
                init = np.asarray(f.init, dtype=f.dtype)
                out[name] = (vals.sum(axis=0, dtype=vals.dtype)
                             - np.asarray(k - 1, dtype=f.dtype) * init
                             ).astype(f.dtype)
        return out

    def demote_replicas(self, key: int) -> int:
        """Fold ``key``'s replica group back to its primary row and free
        the secondaries — the inverse of ``promote_replicas``, under the
        eviction-epoch discipline (attribution retires the secondaries'
        counts per KEY before slot reuse, exactly like eviction).
        Returns the number of secondary rows freed (0 if not
        replicated)."""
        # settle FIRST: a settle-triggered replay may grow/compact this
        # arena and remap the replica dict — pop only once final
        self._settle_owner_chain()
        key = int(key)
        rws = self._replicas.pop(key, None)
        if rws is None:
            return 0
        rws = np.asarray(rws, dtype=np.int64)
        prow = int(rws[0])
        sec = rws[1:]
        merged = self._fold_replica_host(rws)
        dst = jnp.asarray(np.array([prow], dtype=np.int32))
        for name, f in self.info.state_fields.items():
            val = np.asarray(merged[name],
                             dtype=f.dtype).reshape((1, *f.shape))
            self.state[name] = self.state[name].at[dst].set(
                jnp.asarray(val))
        # merge the use clocks: the primary inherits the hottest replica
        dev = np.asarray(host_read("arena", self.last_use_dev[
            jnp.asarray(_pow2_pad(rws, 0))]))[:len(rws)]
        self.last_use_dev = self.last_use_dev.at[dst].max(
            jnp.int32(int(dev.max())))
        self.last_use_tick[prow] = int(self.last_use_tick[rws].max())
        att = self._attribution()
        if att is not None:
            # retire the secondaries' traffic per KEY before the slots
            # can be reused — totals survive demotion exactly as they
            # survive eviction
            att.on_evict(self, sec, np.full(len(sec), key,
                                            dtype=np.int64))
        self._key_of_row[sec] = -1
        self._replica_secondary[sec] = False
        self._free_rows(sec)
        self.replica_demotions += 1
        self.replica_folds += 1
        self._dirty = True
        self._dev_replicas_stale = True
        self.generation += 1
        self.eviction_epoch += 1
        return len(sec)

    # -- elasticity (reference: GrainDirectoryHandoffManager.cs:141) ---------

    def reshard(self, n_shards: int, sharding: Optional[Any] = None) -> None:
        """Re-lay the arena over a different shard count/mesh — the
        tensor-path directory handoff: on membership/mesh change the
        reference merges the dead silo's directory partition into its ring
        successors (reference: GrainDirectoryHandoffManager.cs:141,
        ProcessSiloRemoveEvent); here every row's owner is recomputed from
        the same stable key hash and the state gathers to its new block in
        one scatter per column."""
        self._settle_owner_chain()
        # replication is shard-relative: a new mesh invalidates the
        # spread — fold every group back and let the rebalance
        # controller re-promote from post-reshard telemetry
        for k in list(self._replicas):
            self.demote_replicas(k)
        att = self._attribution()
        if att is not None:
            # fold traffic counts to the host retired mirror while the
            # key→row map still describes the old layout (the mesh may
            # change under us — ledger.relocate's reasoning); counts
            # re-accumulate on the new device set, totals survive per key
            att.fold_type(self.info.name, self)
        live_rows = np.nonzero(self._key_of_row >= 0)[0]
        keys = self._key_of_row[live_rows]
        last_use = self.effective_last_use()[live_rows]
        host_state = self.rows_to_host(live_rows) if len(live_rows) else {}

        # a mesh change re-homes EVERY key by the stable hash: stale
        # migration pins would fight the new layout (and the rebalance
        # controller re-derives moves from post-reshard telemetry)
        self._shard_override = {}
        self._override_sorted = None
        self.n_shards = max(1, n_shards)
        self.sharding = sharding
        per_shard = max(1, -(-max(self.capacity, len(keys) * 2)
                             // self.n_shards))
        self.shard_capacity = per_shard
        self.capacity = per_shard * self.n_shards
        self._key_of_row = np.full(self.capacity, -1, dtype=np.int64)
        self._shard_next = np.zeros(self.n_shards, dtype=np.int64)
        self._free = [np.empty(0, dtype=np.int64)
                      for _ in range(self.n_shards)]
        self.last_use_tick = np.zeros(self.capacity, dtype=np.int64)
        self._replica_secondary = np.zeros(self.capacity, dtype=bool)
        self._dev_replicas = None
        self._dev_replicas_stale = True
        self.live_count = 0
        self._dirty = True
        self._dev_index_stale = True
        self._dev_dense_stale = True
        self._dev_sorted_keys = None
        self._dev_sorted_rows = None
        self._dev_wide = None
        self._dev_wide_stale = True
        self._init_state_columns(self.capacity)
        self.last_use_dev = self._dev_zeros_i32(self.capacity)

        if len(keys):
            store = self.store
            self.store = None  # re-placement is a move, not a re-activation
            try:
                self._activate_keys(keys)
            finally:
                self.store = store
            rows, ok = self.lookup_rows(keys)
            assert ok.all()
            dst = jnp.asarray(rows, dtype=jnp.int32)
            for name, f in self.info.state_fields.items():
                self.state[name] = self.state[name].at[dst].set(
                    jnp.asarray(host_state[name]))
            self.last_use_tick[rows] = last_use
        self.generation += 1

    # -- checkpoint (tick-consistent full-arena write) -----------------------

    def checkpoint(self) -> int:
        """Write every live row through the store — with the engine
        quiesced this is a tick-consistent snapshot of the whole arena,
        stronger than the reference's per-grain-only writes (SURVEY §5
        'checkpoint/resume') while keeping per-grain record granularity."""
        if self.store is None:
            raise RuntimeError(f"arena {self.info.name} has no store")
        live_rows = np.nonzero(self._key_of_row >= 0)[0]
        if self._replicas:
            live_rows = live_rows[~self._replica_secondary[live_rows]]
        if len(live_rows) == 0:
            return 0
        keys = self._key_of_row[live_rows]
        cols = self.rows_to_host(live_rows)
        if self._replicas:
            # a replicated key's stored record is the commutative FOLD —
            # the store never sees replica internals, so a restore into
            # an unreplicated arena is exact
            pos = {int(kk): i for i, kk in enumerate(keys.tolist())}
            for kk, rws in self._replicas.items():
                folded = self._fold_replica_host(rws)
                i = pos[int(kk)]
                for name in cols:
                    cols[name][i] = folded[name]
        self.store.write_many_columnar(self.info.name, keys.tolist(), cols)
        return len(live_rows)

    def restore_from_store(self) -> int:
        """Activate (and load) every key the store holds for this type —
        resume after a process restart."""
        if self.store is None:
            raise RuntimeError(f"arena {self.info.name} has no store")
        keys = self.store.list_keys(self.info.name)
        fresh = keys[~self.lookup_rows(keys)[1]] if len(keys) else keys
        if len(fresh):
            self._activate_keys(fresh)
        return len(fresh)

    # -- durable state plane (tensor/checkpoint.py) --------------------------

    def export_layout(self) -> Dict[str, Any]:
        """Host-side identity metadata of a consistent cut: everything a
        restore needs to reconstruct ROW IDENTITY exactly — the key→row
        map, free-list high-water marks, generation, eviction epoch and
        the host use clock (the device clock rides the pinned state
        tree).  Copies, so the live arena can keep mutating while the
        snapshot drains."""
        return {
            "capacity": int(self.capacity),
            "n_shards": int(self.n_shards),
            "shard_capacity": int(self.shard_capacity),
            "generation": int(self.generation),
            "eviction_epoch": int(self.eviction_epoch),
            "live_count": int(self.live_count),
            "has_wide_keys": bool(self.has_wide_keys),
            "key_of_row": self._key_of_row.copy(),
            "last_use_tick": self.last_use_tick.copy(),
            "shard_next": self._shard_next.copy(),
            # live-migration pins ride the cut: a restore must rebuild
            # placement identity exactly (a migrated grain evicted and
            # reactivated AFTER recovery still lands on its migrated
            # shard).  int-keyed dict of small cardinality — JSON-safe.
            "shard_override": {int(k): int(v) for k, v
                               in self._shard_override.items()},
            # replica groups (primary first): the raw secondary rows ride
            # the pinned state columns, so a kill/recover spanning a
            # promoted interval restores the group bit-exactly.  JSON-safe
            # small dict, like the pins above.
            "replicas": {int(k): [int(x) for x in r]
                         for k, r in self._replicas.items()},
        }

    def _rebuild_free_lists(self) -> None:
        """Free lists from first principles: every sub-high-water slot
        not holding a key is free.  LIFO ORDER is not reconstructed
        (it only biases future allocation toward cache-warm slots, it
        never affects identity) — restored lists are ascending."""
        self._free = []
        for s in range(self.n_shards):
            base = s * self.shard_capacity
            hw = int(self._shard_next[s])
            blk = np.arange(base, base + hw, dtype=np.int64)
            self._free.append(blk[self._key_of_row[blk] < 0])

    def adopt_layout(self, meta: Dict[str, Any], key_of_row: np.ndarray,
                     last_use_tick: np.ndarray,
                     shard_next: np.ndarray, *,
                     init_columns: bool = True,
                     replace: bool = False) -> None:
        """Restore a FULL snapshot's layout onto this (empty, freshly
        restarted) arena: exact key→row map, high-water marks, free
        lists, generation and eviction epoch.  Columns re-initialize to
        field inits; ``scatter_restore`` then lands the snapshot rows.
        ``init_columns=False`` skips the device column (re)allocation —
        the fast-restore path follows with ``adopt_columns`` (one
        host-assembled transfer per column) instead of per-chunk
        scatters, so initializing columns here would be a wasted
        device allocation + fill.  ``replace=True`` permits adoption
        over a NON-empty arena (warm-standby re-base onto a newer full:
        the old columns are dropped wholesale).  A mesh-shape mismatch
        is the caller's to resolve (restore at the recorded layout,
        then ``reshard`` — identity necessarily changes with the
        mesh)."""
        self._settle_owner_chain()
        if self.live_count and not replace:
            raise RuntimeError(
                f"arena {self.info.name}: adopt_layout needs an empty "
                f"arena (restore happens before traffic)")
        recorded_shards = int(meta["n_shards"])
        if recorded_shards != self.n_shards:
            # restore unsharded at the recorded layout; the caller
            # reshards onto the live mesh after the columns land
            self.sharding = None
        self.n_shards = recorded_shards
        self.shard_capacity = int(meta["shard_capacity"])
        self.capacity = int(meta["capacity"])
        self._key_of_row = np.asarray(key_of_row, dtype=np.int64).copy()
        self._shard_next = np.asarray(shard_next, dtype=np.int64).copy()
        self.last_use_tick = np.asarray(last_use_tick,
                                        dtype=np.int64).copy()
        self._rebuild_free_lists()
        self._replicas = {int(k): np.asarray(v, dtype=np.int64)
                          for k, v in meta.get("replicas", {}).items()}
        self._replica_secondary = np.zeros(self.capacity, dtype=bool)
        for r in self._replicas.values():
            self._replica_secondary[r[1:]] = True
        self._dev_replicas = None
        self._dev_replicas_stale = True
        # secondaries occupy slots but are not activations
        self.live_count = int((self._key_of_row >= 0).sum()
                              - self._replica_secondary.sum())
        self.generation = int(meta["generation"])
        self.eviction_epoch = int(meta["eviction_epoch"])
        self.has_wide_keys = bool(meta.get("has_wide_keys", False))
        self._shard_override = {int(k): int(v) for k, v in
                                meta.get("shard_override", {}).items()}
        self._override_sorted = None
        if init_columns:
            self._init_state_columns(self.capacity)
            self.last_use_dev = self._dev_zeros_i32(self.capacity)
        self._dirty = True
        self._dev_index_stale = True
        self._dev_dense_stale = True
        self._dev_wide_stale = True
        self._dev_sorted_keys = None
        self._dev_sorted_rows = None
        self._dev_dense = None
        self._dev_wide = None

    def adopt_columns(self, columns: Dict[str, np.ndarray],
                      last_use_dev: np.ndarray) -> None:
        """Fast-restore companion of ``adopt_layout(init_columns=False)``:
        adopt HOST-assembled full-capacity columns wholesale — one
        ``device_put`` per state column instead of per-chunk device
        scatters.  ``device_put`` dispatches asynchronously, so the
        caller's loop naturally overlaps decoding/assembling column
        N+1 on the host with column N's h2d transfer (the PR 9 staged
        overlap discipline, applied to restore)."""
        new_state: Dict[str, Any] = {}
        for name, f in self.info.state_fields.items():
            col = np.asarray(columns[name])
            want = (self.capacity, *f.shape)
            if col.shape != want or col.dtype != np.dtype(f.dtype):
                raise ValueError(
                    f"arena {self.info.name}: adopt_columns {name} "
                    f"{col.shape}/{col.dtype} != {want}/{f.dtype}")
            new_state[name] = (jax.device_put(col, self.sharding)
                               if self.sharding is not None
                               else jax.device_put(col))
        dev = np.ascontiguousarray(np.asarray(last_use_dev, np.int32))
        if dev.shape != (self.capacity,):
            raise ValueError(
                f"arena {self.info.name}: adopt_columns last_use_dev "
                f"{dev.shape} != ({self.capacity},)")
        self.last_use_dev = (jax.device_put(dev, self.sharding)
                             if self.sharding is not None
                             else jax.device_put(dev))
        self.state = new_state
        self._dirty = True

    def adopt_delta(self, meta: Dict[str, Any], rows: np.ndarray,
                    keys: np.ndarray, live_keys: np.ndarray,
                    shard_next: np.ndarray,
                    last_use_tick: Optional[np.ndarray] = None) -> None:
        """Advance a restored layout by one incremental delta: free keys
        no longer live at the delta's cut, re-home keys that moved slots
        (evict + reactivate between checkpoints), place the dirty
        (row, key) set at its EXACT recorded rows — legal because deltas
        never span a generation change (row moves promote the next
        checkpoint to a full).  Freed slots scrub to field inits, the
        free-list invariant every reuse path assumes."""
        self._settle_owner_chain()
        if int(meta["generation"]) != self.generation \
                or int(meta["capacity"]) != self.capacity:
            raise RuntimeError(
                f"arena {self.info.name}: delta layout mismatch "
                f"(generation {meta['generation']} vs {self.generation})"
                f" — deltas must not span a row move")
        rows = np.asarray(rows, dtype=np.int64)
        keys = np.asarray(keys, dtype=np.int64)
        # 1. keys dead at the delta's cut leave (no write-back — the
        #    snapshot IS the storage)
        cur_live = np.nonzero(self._key_of_row >= 0)[0]
        dead = cur_live[~np.isin(self._key_of_row[cur_live], live_keys)]
        # 2. stale slots of keys that MOVED since the base snapshot
        lookup, found = self.lookup_rows(keys)
        moved = found & (lookup.astype(np.int64) != rows)
        if self._replicas:
            # a secondary row's key looks up to its PRIMARY row — without
            # this guard the primary slot would be freed as "stale"
            moved &= ~self._replica_secondary[rows]
        stale = lookup[moved].astype(np.int64)
        freed = np.unique(np.concatenate([dead, stale]))
        if len(freed):
            self._key_of_row[freed] = -1
            self.last_use_tick[freed] = 0
            idx = jnp.asarray(_pow2_pad(freed, self.capacity))
            for name, f in self.info.state_fields.items():
                self.state[name] = self.state[name].at[idx].set(
                    jnp.full(f.shape, f.init, dtype=f.dtype),
                    mode="drop")
            self.last_use_dev = self.last_use_dev.at[idx].set(
                0, mode="drop")
        # 3. the dirty set lands at its recorded rows
        self._key_of_row[rows] = keys
        if last_use_tick is not None:
            # the delta meta records the FULL host use clock at its cut
            # — without it, restored rows would keep the BASE snapshot's
            # stale clocks and the first idle sweep after recovery could
            # evict rows that were hot at the crash
            self.last_use_tick = np.asarray(last_use_tick,
                                            dtype=np.int64).copy()
        self._shard_next = np.asarray(shard_next, dtype=np.int64).copy()
        self._rebuild_free_lists()
        self.live_count = int((self._key_of_row >= 0).sum()
                              - self._replica_secondary.sum())
        self.eviction_epoch = int(meta["eviction_epoch"])
        if "shard_override" in meta:
            # migrations between pins changed placement identity: the
            # delta's recorded pin set replaces the base snapshot's
            self._shard_override = {int(k): int(v) for k, v in
                                    meta["shard_override"].items()}
            self._override_sorted = None
        self._dirty = True
        self._dev_index_stale = True
        self._dev_dense_stale = True
        self._dev_wide_stale = True

    def scatter_restore(self, rows: np.ndarray,
                        columns: Dict[str, np.ndarray],
                        last_use_dev: np.ndarray) -> None:
        """Land one snapshot chunk: scatter the gathered columns (and
        the device use clock) back at their exact rows.  pow2-padded
        with out-of-range fill so chunk counts reuse O(log n) compiled
        scatters (the ``_free_rows`` discipline)."""
        rows = np.asarray(rows, dtype=np.int64)
        if len(rows) == 0:
            return
        idx = jnp.asarray(_pow2_pad(rows, self.capacity))
        m = len(np.asarray(idx))
        n = len(rows)
        for name, f in self.info.state_fields.items():
            vals = np.zeros((m, *f.shape), dtype=f.dtype)
            vals[:n] = np.asarray(columns[name], dtype=f.dtype)
            self.state[name] = self.state[name].at[idx].set(
                jnp.asarray(vals), mode="drop")
        dev = np.zeros(m, dtype=np.int32)
        dev[:n] = np.asarray(last_use_dev, dtype=np.int32)
        self.last_use_dev = self.last_use_dev.at[idx].set(
            jnp.asarray(dev), mode="drop")

    # -- host access (debug / persistence / host-path interop) --------------

    def read_row(self, key: int) -> Optional[Dict[str, np.ndarray]]:
        if int(key) in self._replicas:
            # replicated grain: the observable state is the fold
            return self._fold_replica_host(self._replicas[int(key)])
        rows, found = self.lookup_rows(np.array([key], dtype=np.int64))
        if not found[0]:
            return None
        r = int(rows[0])
        return {name: np.asarray(col[r]) for name, col in self.state.items()}

    def keys(self) -> np.ndarray:
        live = self._key_of_row >= 0
        if self._replicas:
            live &= ~self._replica_secondary
        return self._key_of_row[live]
