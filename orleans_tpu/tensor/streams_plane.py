"""DeviceSubscriptions: the streams layer's pub-sub adjacency as arena CSR.

Orleans' streams core (PAPER.md: pub-sub over grains — PubSubRendezvous
holds per-stream subscriber sets, pulling agents resolve them and deliver
one grain call per (event, consumer)) is the last per-event host path in
this rebuild.  This module re-imagines it the way dispatch was: the
stream→subscriber graph lives ON DEVICE, maintained under the same
generation/eviction-epoch discipline as every other arena column, and a
whole tick's published events fan out to every subscriber in one
gather + segment_sum.

Two device layouts, one truth:

* **pull CSC (the fast path)** — edges grouped by SUBSCRIBER ARENA ROW
  with row-aligned offsets (``int32[capacity + 1]``): per-tick delivery
  is one gather of the published payload per edge (``edge_src_lane``
  indexes the bound publish key set) followed by a cumulative-sum
  segment reduction straight into the dense state delta.  NO scatter
  touches the device — on scatter-hostile backends (CPU: ~95ns/lane
  serialized) this is the difference between the plane's ≥10M events/s
  and the per-lane floor.  Built against a BOUND publish key set (the
  steady-state injector pattern) and stamped with the subscriber
  arena's ``(generation, eviction_epoch)``.
* **push CSR (the general path)** — edges grouped by STREAM with the
  ragged-expansion kernel shared with ``DeviceFanout``: any publish
  batch (subset publishes, redeliveries, cold-start) expands to
  subscriber KEYS and rides the engine's ordinary device resolution
  (miss-parking auto-activates evicted subscribers, so a deactivated
  consumer still receives — the reference's deliver-reactivates
  semantics).  Overflow lanes park with a device-side dropped mask and
  redeliver with their original ``inject_tick`` (the ShardExchange
  contract).

Churn discipline (the part the property tests hammer):

* subscribe/unsubscribe are HOST mutations buffered into batched,
  vectorized merges — k mutations per tick cost one merge at the next
  rebuild, and a mutation settles the engine's auto-fusion chain first
  so a rolled-back window always replays under the adjacency its ticks
  were buffered with.
* an evicted subscriber row is RETIRED from the adjacency before its
  slot can be reused: the arena's deactivation path calls ``on_evict``
  (before rows return to the free list), which dirties the row layout
  whenever a victim key is subscribed — a publish after the eviction
  rebuilds against the post-eviction layout, so a different grain
  reusing the slot can never receive the dead subscription's events.
  When no victim is subscribed the stamp simply advances (no rebuild:
  rows with edges were untouched).
* rows moving (growth/compaction/reshard) invalidate the stamp by
  construction (generation bump) — the next publish rebuilds.
"""

from __future__ import annotations

import weakref
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from orleans_tpu.tensor.fanout import _expand_kernel
from orleans_tpu.tensor.vector_grain import (
    KEY_SENTINEL,
    ones_mask as _ones_mask,
)


def _as_pairs(streams, subs) -> np.ndarray:
    s = np.asarray(streams, dtype=np.int64).reshape(-1)
    d = np.asarray(subs, dtype=np.int64).reshape(-1)
    if s.shape != d.shape:
        if s.size == 1:
            s = np.broadcast_to(s, d.shape)
        elif d.size == 1:
            d = np.broadcast_to(d, s.shape)
        else:
            raise ValueError("streams/subscribers length mismatch")
    pairs = np.stack([s, d], axis=1)
    if pairs.size and (pairs.min() < 0
                       or pairs.max() >= np.int64(KEY_SENTINEL)):
        raise OverflowError(
            "stream and subscriber keys must be in [0, 2**31-1) — the "
            "device CSR is int32-keyed (hash wider identities in, the "
            "way streams.core.device_stream_key does)")
    return pairs


def _pair_diff(base: np.ndarray, remove: np.ndarray) -> np.ndarray:
    """base \\ remove over [N, 2] pair arrays (vectorized via a packed
    int view — both operands are int31, so packing into one int64 is
    lossless)."""
    if len(base) == 0 or len(remove) == 0:
        return base
    pack = base[:, 0] << np.int64(31) | base[:, 1]
    rpack = remove[:, 0] << np.int64(31) | remove[:, 1]
    return base[~np.isin(pack, rpack, assume_unique=False)]


class DeviceSubscriptions:
    """One stream→subscriber adjacency bound to a subscriber delivery
    edge (``dst_interface.dst_method``) — registered on the engine with
    ``engine.register_subscriptions(src_iface, src_method, subs)`` so
    every message applied to the stream-ingress method also fans out to
    the stream's subscribers."""

    def __init__(self, engine, dst_interface, dst_method: str) -> None:
        self.engine = weakref.ref(engine) if engine is not None else None
        self.type_name = dst_interface if isinstance(dst_interface, str) \
            else dst_interface.__name__
        self.method = dst_method
        # host truth: [E, 2] (stream_key, sub_key) pairs, sorted unique;
        # mutations buffer and merge vectorized at the next rebuild
        self._edges = np.empty((0, 2), dtype=np.int64)
        self._pending_add: List[np.ndarray] = []
        self._pending_remove: List[np.ndarray] = []
        self._sub_keys_sorted = np.empty(0, dtype=np.int64)
        #: bumped on every device-layout rebuild — fused windows bake the
        #: CSR as trace constants and re-trace when this moves
        self.layout_version = 0
        #: bumped on every buffered mutation batch (rebuilds are lazy,
        #: so the fused re-trace predicate needs the PENDING half too)
        self.mutation_version = 0
        self._host_dirty = False
        self._push_dirty = True
        self._pull_dirty = True
        # push CSR (stream-major, dst KEYS)
        self._push: Optional[Tuple] = None
        # parked overflow from the last push expand (engine takes it)
        self._pending_drops: List[Tuple[Any, Any]] = []
        # pull CSC (row-major) against the bound publish key set
        self._bound_keys: Optional[np.ndarray] = None
        self._bound_digest: Optional[Tuple[int, int]] = None
        self._pull: Optional[Dict[str, Any]] = None
        self._pull_stamp: Tuple[int, int] = (-1, -1)
        self._pull_live_count = -1
        self._cold_count = 0
        # host-side stats (the stream.* metric feed)
        self.published_events = 0
        self.delivered_events = 0
        self.pull_deliveries = 0
        self.push_deliveries = 0
        self.rebuilds = 0
        self.retired_edges = 0
        self.dropped_lanes = 0
        self.redeliveries = 0

    # -- control plane (host mutations, batched) -----------------------------

    def _settle_engine_chain(self) -> None:
        """Adjacency mutations settle any outstanding auto-fusion
        verification chain FIRST: a rollback then replays its buffered
        ticks under the adjacency they were consumed with — the
        'rollback restores adjacency state' contract, held structurally
        instead of by snapshotting the CSR."""
        engine = self.engine() if self.engine is not None else None
        if engine is None:
            return
        fuser = getattr(engine, "autofuser", None)
        if fuser is not None and fuser._unverified:
            fuser._settle_chain()

    def subscribe(self, stream_key: int, sub_key: int) -> None:
        self.subscribe_many([stream_key], [sub_key])

    def unsubscribe(self, stream_key: int, sub_key: int) -> None:
        self.unsubscribe_many([stream_key], [sub_key])

    def subscribe_many(self, stream_keys, sub_keys) -> None:
        pairs = _as_pairs(stream_keys, sub_keys)
        if len(pairs) == 0:
            return
        self._settle_engine_chain()
        self._pending_add.append(pairs)
        self._mark_mutated()

    def unsubscribe_many(self, stream_keys, sub_keys) -> None:
        pairs = _as_pairs(stream_keys, sub_keys)
        if len(pairs) == 0:
            return
        self._settle_engine_chain()
        self._pending_remove.append(pairs)
        self._mark_mutated()

    def _mark_mutated(self) -> None:
        self.mutation_version += 1
        self._host_dirty = True
        self._push_dirty = True
        self._pull_dirty = True

    def _merge_host(self) -> None:
        """Fold the buffered mutation batches into the edge table — one
        vectorized merge for any number of buffered calls (removes
        apply AFTER adds, so an add+remove of the same edge within one
        churn window nets to absent)."""
        if not self._host_dirty:
            return
        edges = self._edges
        if self._pending_add:
            edges = np.unique(
                np.concatenate([edges] + self._pending_add), axis=0)
            self._pending_add = []
        if self._pending_remove:
            edges = _pair_diff(
                edges, np.unique(np.concatenate(self._pending_remove),
                                 axis=0))
            self._pending_remove = []
        self._edges = edges
        self._sub_keys_sorted = np.unique(edges[:, 1])
        self._host_dirty = False

    def edges(self) -> np.ndarray:
        """The merged [E, 2] (stream, subscriber) edge table — the host
        truth the exactness oracles replay against."""
        self._merge_host()
        return self._edges

    @property
    def edge_count(self) -> int:
        self._merge_host()
        return len(self._edges)

    def subscribers_of(self, stream_key: int) -> np.ndarray:
        e = self.edges()
        lo = np.searchsorted(e[:, 0], stream_key, side="left")
        hi = np.searchsorted(e[:, 0], stream_key, side="right")
        return e[lo:hi, 1].copy()

    def host_expand(self, stream_keys: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """(dst sub keys, src lane index) of a publish batch, computed
        entirely on host — the oracle replay AND the plane-disabled
        fallback path share this."""
        e = self.edges()
        keys = np.asarray(stream_keys, dtype=np.int64)
        lo = np.searchsorted(e[:, 0], keys, side="left")
        hi = np.searchsorted(e[:, 0], keys, side="right")
        deg = hi - lo
        src_idx = np.repeat(np.arange(len(keys)), deg)
        ranges = [np.arange(a, b) for a, b in zip(lo, hi) if b > a]
        edge_ix = np.concatenate(ranges) if ranges \
            else np.empty(0, dtype=np.int64)
        return e[edge_ix, 1], src_idx

    # -- eviction retirement (the arena hook) --------------------------------

    def on_evict(self, arena, victims: np.ndarray,
                 keys: np.ndarray) -> None:
        """Called by the subscriber arena's deactivation path BEFORE the
        victim rows return to the free list.  A victim that is
        subscribed retires its rows from the device layout (rebuild at
        next publish — the reused slot can never inherit the dead
        subscription); otherwise the pull stamp simply advances to the
        post-eviction epoch (rows holding edges were untouched, so the
        layout stays exactly valid and no rebuild is paid)."""
        if arena.info.name != self.type_name:
            return
        self._merge_host()
        if len(self._sub_keys_sorted) == 0:
            return
        idx = np.searchsorted(self._sub_keys_sorted, keys)
        idx = np.minimum(idx, len(self._sub_keys_sorted) - 1)
        hit = self._sub_keys_sorted[idx] == keys
        if hit.any():
            evicted = keys[hit]
            e = self._edges
            self.retired_edges += int(
                np.isin(e[:, 1], evicted).sum())
            self._pull_dirty = True
            # push CSR holds KEYS, not rows — eviction does not stale it
        elif self._pull is not None \
                and self._pull_stamp == (arena.generation,
                                         arena.eviction_epoch):
            # epoch is about to bump (the caller increments after the
            # hook); adopt it now so the next publish skips the rebuild
            self._pull_stamp = (arena.generation,
                                arena.eviction_epoch + 1)

    def on_migrate(self, arena, keys: np.ndarray) -> None:
        """Called by the subscriber arena's LIVE-MIGRATION path (rows
        move, grains stay live): unlike eviction the subscriptions
        SURVIVE — host truth and the key-addressed push CSR are
        untouched — but the pull layout's per-edge source lanes address
        subscriber ROWS, so any migrated subscribed key dirties it for
        rebuild at the next publish.  With no subscribed mover, only
        the stamp advances (the on_evict discipline: the caller bumps
        the epoch after this hook)."""
        if arena.info.name != self.type_name:
            return
        self._merge_host()
        if len(self._sub_keys_sorted) == 0:
            return
        idx = np.searchsorted(self._sub_keys_sorted, keys)
        idx = np.minimum(idx, len(self._sub_keys_sorted) - 1)
        if (self._sub_keys_sorted[idx] == keys).any():
            self._pull_dirty = True
        elif self._pull is not None \
                and self._pull_stamp == (arena.generation,
                                         arena.eviction_epoch):
            self._pull_stamp = (arena.generation,
                                arena.eviction_epoch + 1)

    # -- pull CSC (the bound fast path) --------------------------------------

    def bind(self, publish_keys: np.ndarray) -> None:
        """Declare the steady-state publish key set (the injector's
        pattern).  Publishes carrying exactly this key set take the
        pull path: per-edge source lanes are precomputed, so a tick's
        fan-out is one payload gather + one cumulative-sum segment
        reduction — zero scatters, zero resolution."""
        keys = np.asarray(publish_keys, dtype=np.int64)
        if len(keys) != len(np.unique(keys)):
            raise ValueError("bound publish keys must be unique")
        self._bound_keys = keys
        self._bound_digest = (len(keys), hash(keys.tobytes()))
        self._pull_dirty = True

    def _matches_bound(self, keys_host: Optional[np.ndarray]) -> bool:
        if self._bound_keys is None or keys_host is None:
            return False
        if keys_host is self._bound_keys:
            return True
        if len(keys_host) != len(self._bound_keys):
            return False
        return (len(keys_host), hash(keys_host.tobytes())) \
            == self._bound_digest

    def _rebuild_pull(self, arena) -> None:
        """Re-lay the CSC against the CURRENT key→row map (one
        vectorized pass): resolve subscriber keys, group live edges by
        destination row, and emit the row-aligned offsets every pull
        delivery reduces over.  Subscribers not live right now are
        COLD: the plane falls back to the push path (whose delivery
        auto-activates them) and re-checks on the next activation."""
        edges = self.edges()
        self._merge_host()
        bound = self._bound_keys
        cap = arena.capacity
        # edges whose stream is outside the bound publish set never
        # receive from this pattern — they stay push-path-only
        in_bound = np.isin(edges[:, 0], bound) if len(edges) else \
            np.zeros(0, bool)
        sel = edges[in_bound]
        rows, found = arena.lookup_rows(sel[:, 1]) if len(sel) else (
            np.empty(0, np.int32), np.empty(0, bool))
        self._cold_count = int((~found).sum())
        live = sel[found]
        live_rows = rows[found].astype(np.int64)
        order = np.argsort(live_rows, kind="stable")
        live = live[order]
        live_rows = live_rows[order]
        # per-edge source lane: position of the edge's stream in the
        # bound key set (vectorized: sort the bound keys once)
        bsort = np.argsort(bound, kind="stable")
        pos = np.searchsorted(bound[bsort], live[:, 0])
        lanes = bsort[np.minimum(pos, len(bound) - 1)] if len(bound) \
            else np.zeros(len(live), np.int64)
        counts = np.bincount(live_rows, minlength=cap) if len(live) \
            else np.zeros(cap, np.int64)
        offsets = np.zeros(cap + 1, dtype=np.int32)
        offsets[1:] = np.cumsum(counts)
        self._pull = {
            "rows": jnp.asarray(live_rows.astype(np.int32)),
            # subscriber KEYS per edge: the stale-batch fallback address
            # (a layout moved between enqueue and execution re-delivers
            # by key through the ordinary device resolution)
            "dst_key": jnp.asarray(live[:, 1].astype(np.int32)),
            "offsets": jnp.asarray(offsets),
            "src_lane": jnp.asarray(lanes.astype(np.int32)),
            "src_key": jnp.asarray(live[:, 0].astype(np.int32)),
            "live_mask": jnp.asarray(counts > 0),
            "n_edges": len(live),
        }
        self._pull_stamp = (arena.generation, arena.eviction_epoch)
        self._pull_live_count = arena.live_count
        self._pull_dirty = False
        self.layout_version += 1
        self.rebuilds += 1

    def pull_layout(self, arena) -> Optional[Dict[str, Any]]:
        """The current pull CSC when it is exactly valid (bound, warm,
        stamps current); None → the caller takes the push path.  A cold
        layout (some subscriber evicted/not yet active) re-checks when
        the arena's live count moves, so a push-delivery reactivation
        promotes the plane back to the fast path on the next publish."""
        if self._bound_keys is None:
            return None
        if (self._pull_dirty or self._pull is None) \
                and isinstance(jnp.int32(0), jax.core.Tracer):
            # never rebuild under an active trace (where even a fresh
            # constant is a tracer): lookup_rows and the jnp.asarray
            # mirrors would be trace-local
            return None
        if self._pull_dirty or self._pull is None \
                or self._pull_stamp != (arena.generation,
                                        arena.eviction_epoch) \
                or (self._cold_count > 0
                    and self._pull_live_count != arena.live_count):
            self._rebuild_pull(arena)
        if self._cold_count > 0:
            return None
        return self._pull

    # -- push CSR (the general path) -----------------------------------------

    def _rebuild_push(self) -> None:
        edges = self.edges()
        streams, starts = np.unique(edges[:, 0], return_index=True) \
            if len(edges) else (np.empty(0, np.int64),
                                np.empty(0, np.int64))
        width = max(256, -(-max(1, len(edges)) // 256) * 256)
        if len(streams) == 0:
            keys_np = np.array([KEY_SENTINEL], np.int32)
            offsets = np.zeros(2, np.int32)
            dst_np = np.full(width, KEY_SENTINEL, np.int32)
        else:
            keys_np = streams.astype(np.int32)
            offsets = np.concatenate(
                [starts, [len(edges)]]).astype(np.int32)
            dst_np = np.full(width, KEY_SENTINEL, np.int32)
            dst_np[:len(edges)] = edges[:, 1].astype(np.int32)
        parts = (jnp.asarray(keys_np), jnp.asarray(offsets),
                 jnp.asarray(dst_np))
        if isinstance(parts[0], jax.core.Tracer):
            self._push_tmp = parts  # trace-local; never cached
            return
        self._push = parts
        self._push_dirty = False
        self.layout_version += 1
        self.rebuilds += 1

    def expand(self, src_keys: jnp.ndarray, args: Any,
               mask: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
        """Push-path ragged expansion — the DeviceFanout contract: (dst
        subscriber keys [width], gathered args + ``src_key``, valid
        mask), with overflowing source lanes parked for the engine's
        redelivery (``take_drop``)."""
        if self._push_dirty or self._push is None:
            self._rebuild_push()
            parts = self._push if self._push is not None \
                else self._push_tmp
        else:
            parts = self._push
        ck, co, cd = parts
        if mask is None:
            mask = _ones_mask(src_keys.shape[0])
        dst, src_index, out_valid, _total, src_dropped, n_dropped = \
            _expand_kernel(ck, co, cd, src_keys, mask, width=cd.shape[0])
        self._pending_drops.append((n_dropped, src_dropped))
        gathered = jax.tree_util.tree_map(
            lambda a: a if jnp.ndim(a) == 0 else jnp.asarray(a)[src_index],
            args)
        if isinstance(gathered, dict) and "src_key" not in gathered:
            gathered = {**gathered, "src_key": src_keys[src_index]}
        return dst, gathered, out_valid

    def take_drop(self) -> Tuple[Any, Any]:
        """(n_dropped, src_dropped) of the expand() that just ran — the
        engine parks these like a miss-check (same as DeviceFanout)."""
        return self._pending_drops.pop()

    def overflow_check(self) -> int:
        drops, self._pending_drops = self._pending_drops, []
        total = 0
        for n_dropped, _mask in drops:
            total += int(n_dropped)
        self.dropped_lanes += total
        return total

    # -- stats ----------------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        return {
            "dst": f"{self.type_name}.{self.method}",
            "edges": self.edge_count,
            "bound": self._bound_keys is not None,
            "cold_subscribers": self._cold_count,
            "layout_version": self.layout_version,
            "rebuilds": self.rebuilds,
            "retired_edges": self.retired_edges,
            "published_events": self.published_events,
            "delivered_events": self.delivered_events,
            "pull_deliveries": self.pull_deliveries,
            "push_deliveries": self.push_deliveries,
            "dropped_lanes": self.dropped_lanes,
            "redeliveries": self.redeliveries,
        }
