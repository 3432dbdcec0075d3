"""DeviceFanout: ragged one-to-many message expansion on device.

The reference's fan-out pattern — one grain holding a variable-size
subscriber set and forwarding each message to every subscriber
(reference: Samples/Chirper/ChirperGrains/ChirperAccount.cs:129-156
PublishMessage → Followers loop; ObserverSubscriptionManager.Notify;
streams' StreamConsumerCollection) — is per-message pointer chasing in
C#.  On TPU the same pattern must become a static-shape gather: the
subscription graph lives as a CSR edge table in device memory, and a
whole batch of published messages expands into one flat (dst_key, args)
tensor in a single jitted kernel.

Raggedness with static shapes: per-message out-degrees are cumsum'd into
offsets, so source lane i owns the output slots [excl[i], offs[i]).
Every lane writes its index at its first slot, one sorted scatter into
``width`` slots, and a running max carries it forward: each slot then
names the last lane with followers that starts at or before it, which
is the lane whose range holds it.  A second scatter writes, at the same
starts, the step between consecutive lanes' CSR-minus-slot offsets, and
a running sum gives every slot its CSR edge with no per-slot gather of
a per-lane table.  That is O(m + width) work, where a per-slot binary
search over the offsets is O(width · log m) rounds of width-wide
gathers.  Slots past the lanes that fit are masked and carry
``KEY_SENTINEL`` keys, which the engine's resolve kernel already drops.

Width rule.  The CSR width is the live edge count rounded up to a lane
multiple of 256 (capped by ``budget``).  A round whose source keys the
host knows (``expand(..., keys_host=...)``: the engine's host-key
publish slabs) gets the exact need from a host mirror of the degrees —
the sum of its lanes' degrees — and expands at the ladder rung at or
above it (``exchange.ladder_ceil``, at least 256, at most the CSR
width).  A per-graph high-water mark holds the widest rung used since
the last rebuild, so the width never shrinks until the graph changes
and the compile set stays one program per rung reached.  Every other
round (device-key sources, redelivery, fused windows) expands at the
full CSR width.

Overflow contract (the ShardExchange discipline, tensor/exchange.py): a
round whose expansion needs more slots than its width loses NOTHING
and raises NOTHING mid-tick.  Source lanes whose whole expansion range
does not fit deliver ZERO slots this round (never a partial prefix —
that would double-deliver on retry) and come back as a device-side
``dropped`` mask; the engine parks it like a miss-check and re-expands
exactly those lanes at the next quiescence point, at the full CSR
width, with their ORIGINAL ``inject_tick`` stamp.  A sized round never
parks below the CSR width: its width is at least its need, so every
lane fits.  Each retry round completes at least one parked lane (a
single lane's degree never exceeds the CSR width, which covers the live
edge count), so convergence is structural.  The storage budget (more
EDGES than ``budget``) remains a hard config error at rebuild.

Mutation (follow/unfollow) is host-side control-plane; the device CSR is
a mirror rebuilt lazily on first expand after a change — the same
truth-on-host / mirror-on-device discipline as the arena's directory
index (arena.py device_index).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from orleans_tpu.tensor.exchange import ladder_ceil
from orleans_tpu.tensor.vector_grain import (
    KEY_SENTINEL,
    ones_mask as _ones_mask,
)

#: row length of the running scans over output slots: one scan along a
#: 786,432-slot axis took ~50 s to compile for a TPU v5e, rows of 1,024
#: and a scan over the rows' ends ~1.6 s
_SCAN_ROW = 1024


def _running(x, scan, combine):
    """Inclusive running ``scan`` (``lax.cummax`` or ``lax.cumsum``,
    with its pairwise ``combine``) of int32 ``x``, in rows of
    ``_SCAN_ROW`` and then across the rows' ends.  0 must be the
    identity of ``combine`` over ``x``'s values."""
    n = x.shape[0]
    row = min(_SCAN_ROW, n)
    y = scan(jnp.pad(x, (0, -n % row)).reshape(-1, row), axis=1)
    carry = scan(y[:, -1])
    carry = jnp.concatenate([jnp.zeros(1, x.dtype), carry[:-1]])
    return combine(y, carry[:, None]).reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("width",))
def _expand_kernel(csr_keys, csr_offsets, csr_dst, src_keys, valid, *,
                   width):
    """Expand [m] source messages into [width] destination slots.

    Returns (dst_keys int32[width], src_index int32[width],
    out_valid bool[width], total int32, src_dropped bool[m],
    n_dropped int32) where ``src_index[j]`` is the source message each
    slot's args are gathered from and ``total`` is the true (unpadded)
    number of expanded messages.  A source lane whose expansion range
    extends past ``width`` materializes NO slots (all-or-nothing per
    lane — a partial prefix would double-deliver on redelivery) and is
    flagged in ``src_dropped`` for the engine's park-and-redeliver
    path.  ``width`` is static and may be shorter than the CSR.

    Each slot finds its lane by a scatter of lane starts and a running
    max, and its CSR edge by a scatter of offset steps and a running
    sum (module docstring).  A masked slot's ``src_index`` is some lane
    in range; nothing reads it."""
    n = csr_keys.shape[0]
    m = src_keys.shape[0]
    idx = jnp.clip(jnp.searchsorted(csr_keys, src_keys), 0, n - 1)
    hit = valid & (csr_keys[idx] == src_keys)
    deg = jnp.where(hit, csr_offsets[idx + 1] - csr_offsets[idx], 0)
    start = jnp.where(hit, csr_offsets[idx], 0)
    offs = jnp.cumsum(deg)                      # inclusive: msgs ≤ i
    total = offs[-1] if offs.shape[0] else jnp.int32(0)
    # all-or-nothing per source lane: lane i's slots are
    # [offs[i]-deg[i], offs[i]) — it fits iff offs[i] <= width
    src_dropped = hit & (deg > 0) & (offs > width)
    n_dropped = jnp.sum(src_dropped.astype(jnp.int32))
    # The lanes that start at or before slot j are a prefix (starts
    # never decrease; those past ``width`` fall off).  Below ``total``
    # its last lane has followers: a zero-degree lane starts where the
    # next lane does, and that lane comes later.  So the prefix's
    # largest lane index, and the sum of its steps of the CSR-edge-
    # minus-slot offset, are those of the lane whose range holds j.
    excl = offs - deg
    zeros = jnp.zeros(width, jnp.int32)
    src_index = _running(
        zeros.at[excl].max(jnp.arange(m, dtype=jnp.int32), mode="drop",
                           indices_are_sorted=True),
        lax.cummax, jnp.maximum)
    step = jnp.diff(start - excl, prepend=0)
    j = jnp.arange(width, dtype=jnp.int32)
    e = j + _running(
        zeros.at[excl].add(step, mode="drop", indices_are_sorted=True),
        lax.cumsum, jnp.add)
    # the lanes that fit are a prefix (offs never decreases)
    fit_end = jnp.max(jnp.where(offs <= width, offs, 0), initial=0)
    out_valid = j < fit_end
    dst = jnp.where(out_valid,
                    csr_dst[jnp.clip(e, 0, max(csr_dst.shape[0] - 1, 0))],
                    KEY_SENTINEL)
    return dst, src_index, out_valid, total, src_dropped, n_dropped


def _group_ranges(sorted_vals: np.ndarray):
    """Yield (value, start, end) for each run of equal values."""
    if len(sorted_vals) == 0:
        return
    boundaries = np.flatnonzero(np.diff(sorted_vals)) + 1
    starts = np.concatenate([[0], boundaries])
    ends = np.concatenate([boundaries, [len(sorted_vals)]])
    for s, e in zip(starts.tolist(), ends.tolist()):
        yield sorted_vals[s], s, e


class FanoutOverflowError(RuntimeError):
    """More STORED edges than the configured budget (a rebuild-time
    config error).  Per-round expansion overflow no longer raises: the
    overflowing source lanes park with a device-side dropped mask and
    re-deliver next tick with their original stamp (the ShardExchange
    contract)."""


class DeviceFanout:
    """A mutable src→{dst...} subscription graph with device expansion.

    ``budget`` caps BOTH the stored edge count and the per-round expansion
    width (one publish round can at most touch every edge once, so a
    single cap covers both)."""

    def __init__(self, budget: int = 1 << 20) -> None:
        self.budget = int(budget)
        self._adj: Dict[int, List[int]] = {}
        self.edge_count = 0
        self._dirty = True
        self._csr_keys: Optional[jnp.ndarray] = None
        self._csr_offsets: Optional[jnp.ndarray] = None
        self._csr_dst: Optional[jnp.ndarray] = None
        # host mirror of the CSR's sources: sorted keys and their
        # degrees, for a round's exact need (``need``) with no device read
        self._host_keys = np.zeros(0, np.int64)
        self._host_deg = np.zeros(0, np.int64)
        self._csr_width = 0
        # widest sized-round rung since the last rebuild
        self._high_water = 0
        # the latest expand()'s parked overflow: (n_dropped device
        # scalar, src_dropped device bool[m]) — consumed by the caller
        # (engine parks a _FanoutCheck; fused folds the count into the
        # window's miss counter).  Un-taken drops accumulate for
        # overflow_check()'s explicit sync.
        self._pending_drops: List[Tuple[Any, Any]] = []
        # cumulative host-side stats, folded at drain points
        self.dropped_lanes = 0
        self.redeliveries = 0
        # rounds sized from their host keys, against full-CSR-width
        # rounds; the sized rounds' exact need and expanded lanes (their
        # padding share is 1 - lanes_needed / lanes_expanded); the
        # latest round's width
        self.sized_rounds = 0
        self.full_width_rounds = 0
        self.lanes_needed = 0
        self.lanes_expanded = 0
        self.width = 0

    # -- control plane (host) ----------------------------------------------

    def follow(self, src: int, dst: int) -> None:
        """Subscribe ``dst`` to ``src``'s messages (reference:
        ChirperAccount.AddFollower)."""
        lst = self._adj.setdefault(int(src), [])
        if int(dst) not in lst:
            lst.append(int(dst))
            self.edge_count += 1
            self._dirty = True

    def unfollow(self, src: int, dst: int) -> None:
        lst = self._adj.get(int(src))
        if lst and int(dst) in lst:
            lst.remove(int(dst))
            self.edge_count -= 1
            self._dirty = True

    def followers_of(self, src: int) -> List[int]:
        return list(self._adj.get(int(src), ()))

    def add_edges(self, src_keys: np.ndarray, dst_keys: np.ndarray) -> None:
        """Bulk graph load (the sample's NetworkLoader analog).

        Vectorized: dedups against BOTH the new batch and existing edges
        with numpy, then extends adjacency lists wholesale — ``follow``'s
        per-edge membership scan is O(degree) and would make a power-law
        celebrity (100k followers) quadratic to load."""
        src = np.asarray(src_keys, dtype=np.int64)
        dst = np.asarray(dst_keys, dtype=np.int64)
        if len(src) == 0:
            return
        pairs = np.unique(np.stack([src, dst], axis=1), axis=0)
        added = 0
        for s, grp_start, grp_end in _group_ranges(pairs[:, 0]):
            lst = self._adj.setdefault(int(s), [])
            new = pairs[grp_start:grp_end, 1].tolist()
            if lst:
                existing = set(lst)
                new = [d for d in new if d not in existing]
            lst.extend(new)
            added += len(new)
        self.edge_count += added
        if added:
            self._dirty = True

    # -- device mirror -------------------------------------------------------

    def _rebuild(self) -> None:
        if self.edge_count > self.budget:
            raise FanoutOverflowError(
                f"{self.edge_count} edges exceed fanout budget {self.budget}")
        srcs = sorted(k for k, v in self._adj.items() if v)
        keys = np.fromiter(srcs, dtype=np.int64, count=len(srcs))
        if (keys >= np.int64(KEY_SENTINEL)).any() or (keys < 0).any():
            raise OverflowError("fanout src keys must be in [0, 2**31-1)")
        # CSR width: the most output slots one round gets, and the width
        # of every round whose host keys are unknown.  Sized to the live
        # edge count (lane-aligned), NOT the storage budget, which stays
        # the hard cap on STORED edges.  A round with known host keys
        # expands at its need's rung instead (``_round_width``), never
        # narrower than the high-water mark, which resets here because
        # the degrees changed.  A lane that does not fit its round parks
        # and re-expands at this full width; width >= any single lane's
        # degree (degree <= edge_count <= width), so every retry round
        # completes at least one lane — convergence is structural.
        width = min(self.budget,
                    max(256, -(-max(1, self.edge_count) // 256) * 256))
        self._host_keys = keys
        self._csr_width = width
        self._high_water = 0
        if not srcs:
            # sentinel row so the kernel never gathers from an empty array;
            # KEY_SENTINEL can't match a valid src key (they are < it)
            keys_np = np.array([KEY_SENTINEL], np.int32)
            offsets = np.zeros(2, np.int32)
            dst_np = np.full(width, KEY_SENTINEL, np.int32)
            self._host_deg = np.zeros(0, np.int64)
        else:
            offsets = np.zeros(len(srcs) + 1, dtype=np.int32)
            dst_np = np.full(width, KEY_SENTINEL, dtype=np.int32)
            pos = 0
            for i, s in enumerate(srcs):
                d = self._adj[s]
                dst_np[pos:pos + len(d)] = d
                pos += len(d)
                offsets[i + 1] = pos
            keys_np = keys.astype(np.int32)
            self._host_deg = np.diff(offsets).astype(np.int64)
        ck = jnp.asarray(keys_np)
        co = jnp.asarray(offsets)
        cd = jnp.asarray(dst_np)
        if isinstance(ck, jax.core.Tracer):
            # built under an abstract trace (fused-tick discovery): the
            # arrays are trace-local — use but never cache them
            return ck, co, cd
        self._csr_keys, self._csr_offsets, self._csr_dst = ck, co, cd
        self._dirty = False
        return ck, co, cd

    # -- data plane ----------------------------------------------------------

    def need(self, keys_host: np.ndarray) -> int:
        """Exact expansion need of a round whose source keys are
        ``keys_host``: the sum of their degrees, from the host mirror.
        Keys with no followers count 0; a duplicate key counts once per
        lane, as the expansion delivers once per lane.  Every lane
        counts, masked or not: an over-estimate only pads."""
        if self._dirty:
            self._rebuild()
        keys = np.asarray(keys_host, dtype=np.int64)
        if len(self._host_keys) == 0 or len(keys) == 0:
            return 0
        idx = np.minimum(np.searchsorted(self._host_keys, keys),
                         len(self._host_keys) - 1)
        hit = self._host_keys[idx] == keys
        return int(self._host_deg[idx[hit]].sum())

    def _round_width(self, need: int) -> int:
        """A sized round's width: its rung, raised to the high-water
        mark so a narrower rung never compiles after a wider one."""
        rung = min(self._csr_width, max(256, ladder_ceil(need)))
        self._high_water = max(self._high_water, rung)
        return self._high_water

    def expand(self, src_keys: jnp.ndarray, args: Any,
               mask: Optional[jnp.ndarray] = None,
               keys_host: Optional[np.ndarray] = None
               ) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
        """(src message keys [m], args pytree [m,...]) → (dst keys
        [width], gathered args [width,...] + ``src_key``, valid mask).

        ``keys_host``, the same source keys on the host, sizes the
        round to its exact need (module docstring); without it the
        round expands at the full CSR width.  Scalar arg leaves
        broadcast (same convention as the engine's kernels).  Source
        lanes whose expansion does not fit this round's width deliver
        NOTHING now; their device-side dropped mask parks via
        ``take_drop()`` (the engine re-expands exactly those lanes at
        the next quiescence point with the original inject stamp — the
        ShardExchange redelivery contract)."""
        if self._dirty:
            ck, co, cd = self._rebuild()
        else:
            ck, co, cd = self._csr_keys, self._csr_offsets, self._csr_dst
        if mask is None:
            mask = _ones_mask(src_keys.shape[0])
        if keys_host is None:
            width = cd.shape[0]
            if not isinstance(src_keys, jax.core.Tracer):
                self.full_width_rounds += 1
        else:
            need = self.need(keys_host)
            width = self._round_width(need)
            self.sized_rounds += 1
            self.lanes_needed += need
            self.lanes_expanded += width
        self.width = width
        dst, src_index, out_valid, _total, src_dropped, n_dropped = \
            _expand_kernel(ck, co, cd, src_keys, mask, width=width)
        self._pending_drops.append((n_dropped, src_dropped))
        gathered = jax.tree_util.tree_map(
            lambda a: a if jnp.ndim(a) == 0 else jnp.asarray(a)[src_index],
            args)
        if isinstance(gathered, dict) and "src_key" not in gathered:
            gathered = {**gathered, "src_key": src_keys[src_index]}
        return dst, gathered, out_valid

    def take_drop(self) -> Tuple[Any, Any]:
        """(n_dropped device scalar, src_dropped device bool[m]) of the
        expand() that just ran — the engine parks these like a
        miss-check; a fused window folds the count into its miss
        counter instead (rollback + unfused replay redelivers)."""
        return self._pending_drops.pop()

    def overflow_check(self) -> int:
        """Synchronize any un-taken parked drop masks (direct expand()
        users — tests, manual drivers) and fold them into the host-side
        ``dropped_lanes`` stat.  Returns the total dropped-lane count
        observed.  No longer raises: per-round overflow re-delivers
        through the engine's park path instead of erroring mid-run."""
        drops, self._pending_drops = self._pending_drops, []
        total = 0
        for n_dropped, _mask in drops:
            total += int(n_dropped)
        self.dropped_lanes += total
        return total

    def snapshot(self) -> Dict[str, Any]:
        return {"width": self.width,
                "sized_rounds": self.sized_rounds,
                "full_width_rounds": self.full_width_rounds,
                "lanes_needed": self.lanes_needed,
                "lanes_expanded": self.lanes_expanded,
                "dropped_lanes": self.dropped_lanes,
                "redeliveries": self.redeliveries}
