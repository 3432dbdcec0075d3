"""WorkloadAttribution: device-resident hot-grain and skew accounting.

Why this exists (ROADMAP item 4's prerequisite): the observability stack
so far is entirely system-centric — the spans say *what* happened
(spans.py), the latency ledger says *how long* it took (ledger.py), the
profiler says *where the cost lives* (profiler.py) — but none of them
can say that ``ChirperAccount/42`` receives 30% of the traffic.  Load-
driven placement and live rebalance (PAPER.md: directory ring +
ActivationCountPlacementDirector) need exactly that *who* signal, and a
per-message host hook would burn the data plane to get it.  This module
accumulates the signal where the traffic lives, with the latency
ledger's discipline: fold inside the tick, one small d2h per snapshot,
never per message.

Three device-resident structures per engine:

* **per-row traffic counts** — one int32 column per arena (sharded like
  the state columns), scatter-added with each executing batch's
  destination rows (``segment_sum`` semantics: the applied-lane mask is
  combined inside the fold, so a masked redelivery lane never counts
  twice).  ``jax.lax.top_k`` over the column at snapshot time yields the
  candidate-row top-K ON DEVICE — only [K] rows + counts cross d2h.
* **a count-min sketch** — int32[depth, width] per arena, the same lanes
  hashed ``depth`` ways (pairwise-independent-ish multiply-shift mixes)
  into ``width`` buckets.  The sketch is the bounded-memory witness:
  its per-key estimate never undercounts, and the classical bound
  ``P[est > true + (e/width)·N] <= exp(-depth)`` prices the HotSet's
  ``confidence`` — the counts column can be evicted/remapped, the sketch
  keeps absorbing, and a reader knows exactly how much to trust it.
* **per-(type, method) slot counts** — int32[MAX_SLOTS] sharing the
  latency ledger's SlotRegistry, so traffic share per method costs one
  scatter-add in the same fold.

The fold is ONE jit dispatch per executing (type, method) group on the
unfused path, and it must cost ~nothing: a per-lane scatter per batch
measured ~50ns/lane on the CPU backend — 2.5x the whole tick at 20k
lanes, where the acceptance bar is <5%.  The unfused engine's steady
state saves us: an injector re-presents the SAME device (rows, mask)
arrays every tick (the identity the whole engine keys caching on), so
the fold memoizes a **dense delta plan** per (rows, mask) identity —
bincount of the valid lanes + the sketch's hashed delta, built once by
``_plan_kernel`` — and the steady-state dispatch is three vectorized
adds (``_apply_kernel``, donated in, async, no sync).  Device arrays
are immutable, so identity implies content; numpy inputs are never
memoized (hosts can mutate buffers in place — the PR 9 staging-memo
lesson).  A novel batch pays one scatter-shaped plan build, measured in
the bench oracle tier.  Inside fused windows the fold inlines into the
``lax.scan`` as the plain scatter (``fold_batch``) exactly like the
ledger hist — integer adds are exactly associative, so the two paths
are bit-identical — autofuse's AOT lower includes the accumulator
avals, windows return them undonated, and a rolled-back chain restores
the pre-chain arrays so the unfused replay re-records exactly once
(``snapshot_state``/``restore_state``, the ledger contract).

Eviction epochs: free-list deactivation frees rows without moving
survivors, and a freed slot may be *reused by a different grain* — a
per-row count that outlived its grain would misattribute.  The arena's
deactivation path therefore RETIRES victims through ``on_evict``: their
counts gather to a host-side ``{key: count}`` mirror (one small d2h per
eviction chunk, riding a path that is already host-synchronous) and the
rows zero on device before reuse.  Snapshots merge live + retired per
key, so per-grain totals survive eviction epochs bit-exactly.  Row moves
(growth/compaction) remap the column on device (``remap_rows``, the
``last_use_dev`` discipline); a mesh reshard folds to the host mirror
first (``fold_type`` — the compiled arrays are committed to the old
device set, same as ``ledger.relocate``).

The host half resolves candidate rows back to grain keys via the arena
mirror (``_key_of_row``) and publishes a **HotSet** — ``[(key, msgs,
share, sketch_est, confidence)]`` — plus per-arena skew gauges
(max-shard share, Gini over live rows, p99-to-mean) computed on device
at snapshot time.  ``silo.collect_metrics`` mirrors all of it into the
``hot.*``/``skew.*`` catalog rows, the load publisher broadcasts the
HotSet with its runtime statistics, and the dashboard renders the
hot-grains/skew rows — the signal ROADMAP item 4's rebalancer consumes
unchanged.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from orleans_tpu.tensor.arena import _pow2_pad
from orleans_tpu.tensor.ledger import MAX_SLOTS, SlotRegistry

#: multiply-shift seed per sketch depth (odd constants; depth is capped
#: by the seed count — 8 depths drive the failure probability to e^-8)
CMS_SEEDS = (0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F,
             0x165667B1, 0xD3A2646D, 0xFD7046C5, 0xB55A4F09)
MAX_CMS_DEPTH = len(CMS_SEEDS)


def pow2ceil(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def cms_hash(rows, seeds, width: int):
    """[depth, m] sketch buckets of ``rows`` (device twin used by both
    the fold and the snapshot estimator — MUST stay consistent)."""
    u = rows.astype(jnp.uint32)[None, :] * seeds[:, None]
    u = u ^ (u >> 15)
    u = u * jnp.uint32(0x27D4EB2F)
    u = u ^ (u >> 13)
    return (u & jnp.uint32(width - 1)).astype(jnp.int32)


def fold_batch(counts, cms, slots, seeds, slot, rows, valid):
    """One batched attribution fold (traceable — the fused tick program
    inlines this inside its scan): combine the applied-lane mask (valid
    ∧ rows in range), scatter-add the lanes into (a) the per-row traffic
    column, (b) every sketch depth's hashed bucket, and (c) the
    (type, method) slot counter.  Invalid lanes add zero everywhere."""
    cap = counts.shape[0]
    rows = jnp.asarray(rows, jnp.int32)
    valid = jnp.asarray(valid, bool) & (rows >= 0) & (rows < cap)
    inc = valid.astype(jnp.int32)
    r = jnp.where(valid, rows, cap)  # out-of-range + mode="drop"
    counts = counts.at[r].add(inc, mode="drop")
    depth = cms.shape[0]
    h = cms_hash(rows, seeds, cms.shape[1])
    cms = cms.at[jnp.arange(depth, dtype=jnp.int32)[:, None], h].add(
        inc[None, :])
    slots = slots.at[slot].add(jnp.sum(inc))
    return counts, cms, slots


def fold_counts(counts, slots, slot, rows, valid, segments=None):
    """The scan-carry half of an in-window fold: per-row counts + the
    (type, method) slot counter, WITHOUT the sketch — the fused window
    folds the CMS once per window from the counts delta
    (``fold_cms_dense``), which removes a lane-sized sketch scatter
    from every scanned tick.  Integer adds commute, so the split is
    bit-identical to per-lane ``fold_batch`` calls.

    ``segments`` (a pull-mode delivery batch's row-aligned offsets,
    tensor/streams_plane.py) switches the counts fold to the same
    scatter-free cumulative-sum reduction the delivery handler uses."""
    inc_src = jnp.asarray(valid, bool)
    if segments is not None:
        inc = inc_src.astype(jnp.int32)
        z = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(inc)])
        counts = counts + (z[segments[1:]] - z[segments[:-1]])
        slots = slots.at[slot].add(jnp.sum(inc))
        return counts, slots
    cap = counts.shape[0]
    rows = jnp.asarray(rows, jnp.int32)
    valid = inc_src & (rows >= 0) & (rows < cap)
    inc = valid.astype(jnp.int32)
    r = jnp.where(valid, rows, cap)  # out-of-range + mode="drop"
    counts = counts.at[r].add(inc, mode="drop")
    slots = slots.at[slot].add(jnp.sum(inc))
    return counts, slots


def fold_cms_dense(cms, counts_delta, seeds):
    """Sketch fold from a DENSE per-row delta: one capacity-sized
    scatter covering any number of per-tick, per-group lane folds —
    the per-row sums land in exactly the hashed buckets ``fold_batch``
    would have scattered lane by lane (the hash is row-keyed and adds
    commute), so the result is bit-identical."""
    depth, width = cms.shape
    cap = counts_delta.shape[0]
    h = cms_hash(jnp.arange(cap, dtype=jnp.int32), seeds, width)
    return cms.at[jnp.arange(depth, dtype=jnp.int32)[:, None], h].add(
        counts_delta[None, :].astype(jnp.int32))


@partial(jax.jit, static_argnames=("cap", "width", "depth"))
def _plan_kernel(rows, valid, seeds, cap: int, width: int, depth: int):
    """Build one batch's dense delta plan: bincount of the valid lanes
    over the counts column's support + the sketch's hashed delta + the
    lane total.  Paid ONCE per (rows, mask) identity (injector steady
    state) or per call for novel batches — the scatters live here, off
    the steady-state hot path."""
    rows = jnp.asarray(rows, jnp.int32)
    valid = jnp.asarray(valid, bool) & (rows >= 0) & (rows < cap)
    inc = valid.astype(jnp.int32)
    r = jnp.where(valid, rows, cap)  # out-of-range lanes park at cap
    counts_delta = jnp.zeros(cap + 1, jnp.int32).at[r].add(inc)[:cap]
    h = cms_hash(rows, seeds, width)
    cms_delta = jnp.zeros((depth, width), jnp.int32).at[
        jnp.arange(depth, dtype=jnp.int32)[:, None], h].add(inc[None, :])
    return counts_delta, cms_delta, jnp.sum(inc)


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _apply_coalesced(counts, cms, slots, counts_delta, cms_delta, slot,
                     n, k):
    """Flush a run of ``k`` host-proven folds of ONE plan: integer
    multiply-adds are exactly k repeated adds, so coalescing is
    bit-exact.  Donated accumulators (double-buffered in place — safe
    because fused windows never donate their attribution inputs, and no
    unfused fold can run mid-chain: any pattern break settles the chain
    first, flushing this buffer)."""
    return (counts + k * counts_delta, cms + k * cms_delta,
            slots.at[slot].add(k * n))


@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _apply_checked_stack(counts, cms, slots, stale, plan_rows,
                         plan_valid, counts_delta, cms_delta, n, seeds,
                         slot, rows_stack, valid_stack, real):
    """Flush a stack of device-checked folds against ONE plan: emit
    batches' rows are jit program OUTPUTS — fresh buffers every tick
    even when the values never change — so no host-side identity can
    prove the plan applies.  The device proves it instead: one batched
    exact compare counts the matching occurrences (k·delta fast path),
    and each mismatched occurrence takes the full scatter fold inside a
    ``lax.scan`` step while bumping the stale counter the next snapshot
    reads to refresh the plan.  ``real`` masks the pow2 padding rows
    (no-ops on both paths).  Exactness is unconditional; only the cost
    depends on the guess."""
    rows_stack = jnp.asarray(rows_stack, jnp.int32)
    valid_stack = jnp.asarray(valid_stack, bool)
    matches = real \
        & jnp.all(rows_stack == plan_rows[None, :], axis=1) \
        & jnp.all(valid_stack == plan_valid[None, :], axis=1)
    km = jnp.sum(matches.astype(jnp.int32))
    counts = counts + km * counts_delta
    cms = cms + km * cms_delta
    slots = slots.at[slot].add(km * n)
    mismatch = real & ~matches

    def body(carry, x):
        c, s, sl, st = carry
        r, v, mm = x

        def miss(_):
            c2, s2, sl2 = fold_batch(c, s, sl, seeds, slot, r, v)
            return c2, s2, sl2, st + 1

        return jax.lax.cond(mm, miss, lambda _: (c, s, sl, st),
                            None), None

    (counts, cms, slots, stale), _ = jax.lax.scan(
        body, (counts, cms, slots, stale),
        (rows_stack, valid_stack, mismatch))
    return counts, cms, slots, stale


#: bound on the (rows, mask) → delta-plan memo (cleared wholesale past
#: it, the ones_mask cache discipline)
_MAX_PLANS = 128

#: buffered folds flushed per coalesced dispatch (the amortization
#: window: steady state pays one dispatch per _FLUSH_CAP folds instead
#: of one per executing group)
_FLUSH_CAP = 32


@partial(jax.jit, static_argnames=("k", "n_shards"))
def _snapshot_kernel(counts, cms, seeds, k: int, n_shards: int):
    """Device-side snapshot of one arena: candidate top-K, per-shard
    sums, and the skew gauges — everything reduced ON DEVICE so the d2h
    transfer is a handful of tiny arrays, never the counts column."""
    total = jnp.sum(counts)
    vals, rows = jax.lax.top_k(counts, k)
    shard = jnp.sum(counts.reshape(n_shards, -1), axis=1)
    s = jnp.sort(counts)
    nz = s > 0
    nnz = jnp.sum(nz)
    nnz_f = jnp.maximum(nnz, 1).astype(jnp.float32)
    # Gini over the LIVE (nonzero) rows: sorted ascending, the zeros
    # occupy ranks below every live row, so rank-within-nonzero is the
    # running cumsum of the nonzero mask
    rank = jnp.cumsum(nz.astype(jnp.int32))
    g = jnp.where(nz, (2.0 * rank - nnz_f - 1.0) * s.astype(jnp.float32),
                  0.0)
    total_f = jnp.maximum(total, 1).astype(jnp.float32)
    gini = jnp.sum(g) / (nnz_f * total_f)
    cap = counts.shape[0]
    pos = jnp.clip(cap - nnz + ((nnz - 1) * 99) // 100, 0, cap - 1)
    p99 = s[pos]
    mean_nz = total_f / nnz_f
    est = jnp.min(cms[jnp.arange(cms.shape[0], dtype=jnp.int32)[:, None],
                      cms_hash(rows, seeds, cms.shape[1])], axis=0)
    return vals, rows, shard, total, gini, p99, mean_nz, nnz, est


@jax.jit
def _gather_counts(counts, rows):
    """Small pow2-padded gather for eviction retirement / candidate
    cross-checks (the padding rows gather row 0; callers slice)."""
    return counts[jnp.clip(rows, 0, counts.shape[0] - 1)]


@jax.jit
def _zero_rows(counts, rows):
    return counts.at[rows].set(0, mode="drop")


class WorkloadAttribution:
    """Per-engine workload attribution plane (see module docstring).

    Accumulator lifecycle mirrors DeviceLatencyLedger: arrays are
    created lazily at the arena's current capacity, ride fused windows
    as undonated carry, snapshot/restore for rollback, and fold to host
    on reshard.  ``d2h_fetches`` counts snapshot transfers (the budget
    test pins one per snapshot call)."""

    def __init__(self, engine, enabled: bool = True, top_k: int = 16,
                 cms_depth: int = 4, cms_width: int = 8192,
                 slots: Optional[SlotRegistry] = None) -> None:
        self.engine = engine
        self.enabled = enabled
        self.top_k = max(1, int(top_k))
        self.cms_depth = max(1, min(int(cms_depth), MAX_CMS_DEPTH))
        self.cms_width = pow2ceil(max(16, int(cms_width)))
        self.slots = slots if slots is not None else SlotRegistry()
        self._counts: Dict[str, jnp.ndarray] = {}   # type → int32[capacity]
        self._cms: Dict[str, jnp.ndarray] = {}      # type → [depth, width]
        self._slot_counts: Optional[jnp.ndarray] = None  # int32[MAX_SLOTS]
        self._seeds: Optional[jnp.ndarray] = None
        # host mirror of counts RETIRED off the device column (eviction,
        # reshard): per type, grain key → messages.  Merged per key at
        # snapshot so totals survive eviction epochs bit-exactly.
        self._retired: Dict[str, Dict[int, int]] = {}
        self.records = 0
        self.d2h_fetches = 0
        self.retired_rows = 0
        self._retire_version = 0
        self._snap_cache: Optional[Tuple[Tuple[int, int], Dict]] = None
        # (type, method) → (anchor, mask, epoch, plan): the dense delta
        # plans; entries hold the anchoring arrays so a recycled id can
        # never alias a dead buffer, and plan = (rows, valid,
        # counts_delta, cms_delta, n) with the baked content the
        # checked kernel verifies on device
        self._plans: Dict[Tuple[str, str], Tuple] = {}
        self._stale: Optional[jnp.ndarray] = None  # device mismatch count
        self._last_stale = 0
        self._slot_dev: Dict[int, jnp.ndarray] = {}  # slot → device scalar
        # buffered (type, slot, plan, rows, mask, checked) folds —
        # flushed coalesced on the cap or before any accumulator read
        self._pending: List[Tuple] = []
        self.plan_hits = 0
        self.plan_checked = 0
        self.plan_builds = 0

    # -- configuration -------------------------------------------------------

    def configure(self, enabled: Optional[bool] = None,
                  top_k: Optional[int] = None,
                  cms_depth: Optional[int] = None,
                  cms_width: Optional[int] = None) -> None:
        """Live-reload surface (silo.update_config re-push).  Changing
        the sketch layout resets the accumulated sketch (its shape is
        part of every compiled fold signature); the counts columns and
        retired mirror survive a top_k/enable change untouched."""
        self.flush_folds()  # buffered folds assume the OLD layout
        if enabled is not None:
            self.enabled = enabled
        if top_k is not None:
            self.top_k = max(1, int(top_k))
        reshape = False
        if cms_depth is not None:
            d = max(1, min(int(cms_depth), MAX_CMS_DEPTH))
            reshape |= d != self.cms_depth
            self.cms_depth = d
        if cms_width is not None:
            w = pow2ceil(max(16, int(cms_width)))
            reshape |= w != self.cms_width
            self.cms_width = w
        if reshape:
            self._cms = {}
            self._seeds = None
            self._plans = {}  # plans bake the sketch layout
        self._snap_cache = None

    def build_signature(self) -> Tuple:
        """What a fused window bakes in: a change re-traces (cause
        config_toggle), the prepare() discipline the ledger set."""
        return (self.enabled, self.cms_depth, self.cms_width)

    def reset(self) -> None:
        """Zero everything (bench A/B segment boundaries)."""
        self._pending = []  # zeroed with the accumulators they target
        self._counts = {}
        self._cms = {}
        self._slot_counts = None
        self._retired = {}
        self._retire_version += 1
        self._snap_cache = None

    # -- accumulator access --------------------------------------------------

    def _seed_arr(self) -> jnp.ndarray:
        if self._seeds is None:
            seeds = jnp.asarray(
                np.asarray(CMS_SEEDS[:self.cms_depth], dtype=np.uint32))
            if isinstance(seeds, jax.core.Tracer):
                # created under an abstract trace (fused discovery):
                # trace-local — caching would leak (arena.device_index's
                # guard, applied to every lazy array here)
                return seeds
            self._seeds = seeds
        return self._seeds

    def counts_for(self, type_name: str) -> jnp.ndarray:
        col = self._counts.get(type_name)
        arena = self.engine.arenas.get(type_name)
        cap = arena.capacity if arena is not None \
            else self.engine.initial_capacity
        if col is None or col.shape[0] != cap:
            if col is not None:
                # capacity changed without a remap/fold hook firing
                # (direct arena surgery in tests): fold what we can
                self.fold_type(type_name)
            col = arena._dev_zeros_i32(cap) if arena is not None \
                else jnp.zeros(cap, jnp.int32)
            if isinstance(col, jax.core.Tracer):
                return col  # trace-local (see _seed_arr)
            self._counts[type_name] = col
        return col

    def cms_for(self, type_name: str) -> jnp.ndarray:
        sk = self._cms.get(type_name)
        if sk is None or isinstance(sk, np.ndarray):
            # a numpy entry is a relocated sketch (host-parked across a
            # mesh reshard) — re-upload on the current device set
            sk = jnp.asarray(sk) if sk is not None else \
                jnp.zeros((self.cms_depth, self.cms_width), jnp.int32)
            if isinstance(sk, jax.core.Tracer):
                return sk  # trace-local (see _seed_arr)
            self._cms[type_name] = sk
        return sk

    def _slot_arr(self) -> jnp.ndarray:
        if self._slot_counts is None or \
                isinstance(self._slot_counts, np.ndarray):
            slots = jnp.asarray(self._slot_counts) \
                if self._slot_counts is not None \
                else jnp.zeros(MAX_SLOTS, jnp.int32)
            if isinstance(slots, jax.core.Tracer):
                return slots  # trace-local (see _seed_arr)
            self._slot_counts = slots
        return self._slot_counts

    # -- hot path ------------------------------------------------------------

    def _stale_arr(self) -> jnp.ndarray:
        if self._stale is None:
            stale = jnp.zeros((), jnp.int32)
            if isinstance(stale, jax.core.Tracer):
                return stale  # trace-local (see _seed_arr)
            self._stale = stale
        return self._stale

    def _slot_scalar(self, slot: int) -> jnp.ndarray:
        """Device scalar per slot, cached — a per-fold ``jnp.int32``
        literal costs a small h2d on every dispatch (bounded: slots are
        capped at MAX_SLOTS)."""
        s = self._slot_dev.get(slot)
        if s is None:
            s = jnp.int32(slot)
            if isinstance(s, jax.core.Tracer):
                return s  # trace-local (see _seed_arr)
            self._slot_dev[slot] = s
        return s

    def record_group(self, arena, type_name: str, method: str,
                     rows, mask, ident=None) -> None:
        """One executing (type, method) group's fold — the engine's
        dispatch-phase accumulation point.  Steady state costs a host
        list append: the fold is BUFFERED (with its resolved delta
        plan) and flushed as coalesced device kernels on the buffer cap
        or before any read — integer adds commute, so k buffered folds
        of one plan land as one ``k·delta`` multiply-add, bit-exact.
        A plan is proven applicable one of two ways:

        * **host-proven** — the batch's anchor (``ident``: the stable
          ``keys_dev`` buffer, else ``rows`` itself on the injector
          fast path) is the SAME immutable device array the plan was
          built from, and for ident-anchored plans the arena's
          (generation, eviction_epoch, live_count) triple is unchanged
          so the key→row map cannot have moved.
        * **device-checked** — emit batches' rows are jit program
          outputs (fresh buffers every tick even at constant values):
          the flush kernel compares content on device and falls back
          to the full scatter fold in-kernel on mismatch, bumping a
          stale counter the next snapshot reads to refresh the plan.

        A novel batch builds its plan (the one scatter-shaped cost,
        measured in the bench oracle tier) at record time."""
        if not self.enabled:
            return
        slot = self.slots.slot_for(type_name, method)
        counts = self.counts_for(type_name)
        cms = self.cms_for(type_name)
        anchor = rows if ident is None else ident
        epoch = (arena.generation, arena.eviction_epoch,
                 arena.live_count) if arena is not None else None
        key = (type_name, method)
        entry = self._plans.get(key)
        plan = None
        checked = False
        if entry is not None:
            e_anchor, e_mask, e_epoch, e_plan = entry
            shapes_ok = (e_plan[2].shape[0] == counts.shape[0]
                         and e_plan[3].shape == cms.shape
                         and getattr(rows, "shape", None)
                         == e_plan[0].shape)
            if shapes_ok and e_anchor is anchor and e_mask is mask \
                    and (ident is None or e_epoch == epoch):
                plan = e_plan
                self.plan_hits += 1
            elif shapes_ok and isinstance(rows, jax.Array) \
                    and isinstance(mask, jax.Array):
                plan = e_plan
                checked = True
                self.plan_checked += 1
        if plan is None:
            rows_d = jnp.asarray(rows, jnp.int32)
            mask_d = jnp.asarray(mask, bool)
            delta = _plan_kernel(rows_d, mask_d, self._seed_arr(),
                                 cap=counts.shape[0],
                                 width=cms.shape[1],
                                 depth=cms.shape[0])
            plan = (rows_d, mask_d) + delta
            rows, mask = rows_d, mask_d
            self.plan_builds += 1
            if isinstance(anchor, jax.Array) \
                    and isinstance(mask, jax.Array):
                if len(self._plans) >= _MAX_PLANS:
                    self._plans.clear()
                self._plans[key] = (anchor, mask, epoch, plan)
        self._pending.append((type_name, slot, plan, rows, mask,
                              checked))
        self.records += 1
        self._snap_cache = None
        if len(self._pending) >= _FLUSH_CAP:
            self.flush_folds()

    def flush_folds(self) -> None:
        """Apply every buffered fold in coalesced device kernels: runs
        of one plan collapse to a single ``k·delta`` multiply-add
        (host-proven) or one stacked compare + per-mismatch scatter
        scan (device-checked).  Re-entrant safe (the buffer swaps out
        first); called on the buffer cap and before ANY read or
        row-lifecycle mutation of the accumulators."""
        if not self._pending:
            return
        pending, self._pending = self._pending, []

        def traced(result) -> bool:
            # under an ACTIVE trace (fused window trace, AOT lower,
            # discovery eval_shape) a jit call inlines into the outer
            # trace and returns TRACERS — storing those would poison
            # the accumulators for every later concrete call.  Defer:
            # the pre-run device_state_in / the next concrete read
            # flushes (traces only need avals, and shapes don't move).
            # The trace state cannot change inside this call, so only
            # the FIRST kernel result can be a tracer, before any store.
            if not isinstance(result, jax.core.Tracer):
                return False
            self._pending = pending + self._pending
            return True

        groups: Dict[Tuple, List] = {}
        order: List[Tuple] = []
        for e in pending:
            key = (e[0], e[1], id(e[2]), e[5])
            if key not in groups:
                groups[key] = []
                order.append(key)
            groups[key].append(e)
        for key in order:
            entries = groups[key]
            type_name, slot, _pid, checked = key
            counts = self.counts_for(type_name)
            cms = self.cms_for(type_name)
            plan = entries[0][2]
            plan_rows, plan_valid, cdelta, sdelta, n = plan
            if cdelta.shape[0] != counts.shape[0] \
                    or sdelta.shape != cms.shape:
                # accumulator layout changed under the buffer (live
                # sketch reconfigure, direct arena surgery): replay
                # each fold from its retained ground-truth rows — the
                # rows are the truth in BOTH regimes, so a rebuilt plan
                # applies proven
                for e in entries:
                    d = _plan_kernel(
                        jnp.asarray(e[3], jnp.int32),
                        jnp.asarray(e[4], bool), self._seed_arr(),
                        cap=counts.shape[0], width=cms.shape[1],
                        depth=cms.shape[0])
                    counts, cms, slots = _apply_coalesced(
                        counts, cms, self._slot_arr(), d[0], d[1],
                        self._slot_scalar(slot), d[2], jnp.int32(1))
                    if traced(counts):
                        return
                    self._counts[type_name] = counts
                    self._cms[type_name] = cms
                    self._slot_counts = slots
                continue
            stale = None
            if checked:
                k = len(entries)
                pad = pow2ceil(k)
                rows_stack = jnp.stack(
                    [jnp.asarray(e[3], jnp.int32) for e in entries]
                    + [plan_rows] * (pad - k))
                valid_stack = jnp.stack(
                    [jnp.asarray(e[4], bool) for e in entries]
                    + [plan_valid] * (pad - k))
                real = jnp.asarray(
                    np.arange(pad) < k)
                counts, cms, slots, stale = _apply_checked_stack(
                    counts, cms, self._slot_arr(), self._stale_arr(),
                    plan_rows, plan_valid, cdelta, sdelta, n,
                    self._seed_arr(), self._slot_scalar(slot),
                    rows_stack, valid_stack, real)
            else:
                counts, cms, slots = _apply_coalesced(
                    counts, cms, self._slot_arr(), cdelta, sdelta,
                    self._slot_scalar(slot), n,
                    jnp.int32(len(entries)))
            if traced(counts):
                return
            if stale is not None:
                self._stale = stale
            self._counts[type_name] = counts
            self._cms[type_name] = cms
            self._slot_counts = slots

    # -- fused-program integration -------------------------------------------

    def device_state_in(self, touched: List[str]) -> Dict[str, Any]:
        """The accumulator pytree handed INTO a fused window program
        (tensor/fused.py threads it through the scan; empty when the
        plane is disabled so the window signature stays stable)."""
        if not self.enabled:
            return {}
        self.flush_folds()  # the window must see every recorded fold
        return {"counts": {t: self.counts_for(t) for t in touched},
                "cms": {t: self.cms_for(t) for t in touched},
                "slots": self._slot_arr()}

    def device_state_out(self, attr: Dict[str, Any]) -> None:
        if not attr:
            return
        self._counts.update(attr["counts"])
        self._cms.update(attr["cms"])
        self._slot_counts = attr["slots"]
        self.records += 1
        self._snap_cache = None

    def snapshot_state(self) -> Tuple:
        """Rollback pin for the auto-fuser's verification chain: array
        references are safe to hold — fused windows never donate their
        attribution inputs, and no unfused fold can run mid-chain (the
        ledger's snapshot_state invariant)."""
        self.flush_folds()  # pin post-flush arrays; none recorded mid-chain
        return (dict(self._counts), dict(self._cms), self._slot_counts,
                {t: dict(d) for t, d in self._retired.items()},
                self.retired_rows)

    def restore_state(self, state: Tuple) -> None:
        """Undo every fold since ``snapshot_state`` — a rolled-back
        window's unfused replay re-records every message."""
        (self._counts, self._cms, self._slot_counts,
         self._retired, self.retired_rows) = state
        self._retire_version += 1
        self._snap_cache = None

    # -- row lifecycle hooks (arena calls these) -----------------------------

    def has_state(self, type_name: str) -> bool:
        return type_name in self._counts

    def on_evict(self, arena, victims: np.ndarray,
                 keys: np.ndarray) -> None:
        """Retire evicted rows' counts to the host mirror before their
        slots return to the free list (a reused slot must never inherit
        the evicted grain's traffic).  One small gather d2h per eviction
        chunk — the deactivation path is already host-synchronous."""
        self.flush_folds()  # retire POST-fold counts, not a stale column
        col = self._counts.get(arena.info.name)
        if col is None or len(victims) == 0:
            return
        idx = _pow2_pad(victims.astype(np.int32), 0)
        vals = np.asarray(_gather_counts(col, jnp.asarray(idx)))[
            :len(victims)]
        retired = self._retired.setdefault(arena.info.name, {})
        nz = vals > 0
        for k, v in zip(keys[nz].tolist(), vals[nz].tolist()):
            retired[k] = retired.get(k, 0) + int(v)
        self._counts[arena.info.name] = _zero_rows(
            col, jnp.asarray(_pow2_pad(
                victims.astype(np.int32), col.shape[0])))
        self.retired_rows += len(victims)
        self._retire_version += 1
        self._snap_cache = None

    def remap_rows(self, arena, old_rows: np.ndarray,
                   new_rows: np.ndarray, new_capacity: int) -> None:
        """Row move (growth/compaction): relocate the counts on device,
        the ``last_use_dev`` discipline — no transfer, keys keep their
        totals."""
        self.flush_folds()  # buffered folds target the OLD row layout:
        # applying them after the move would scatter into rows that are
        # now free or owned by other grains (the flush-before-any-
        # row-lifecycle-mutation rule on_evict/fold_type already follow)
        col = self._counts.get(arena.info.name)
        if col is None:
            return
        idx = jnp.asarray(old_rows, jnp.int32)
        dst = jnp.asarray(new_rows, jnp.int32)
        self._counts[arena.info.name] = \
            arena._dev_zeros_i32(new_capacity).at[dst].set(col[idx])
        self._snap_cache = None

    def fold_type(self, type_name: str, arena=None) -> None:
        """Fold one arena's device counts into the host retired mirror
        and drop the column (mesh reshard: the array is committed to the
        old device set — ledger.relocate's reasoning).  Idempotent."""
        self.flush_folds()
        col = self._counts.pop(type_name, None)
        if col is None:
            return
        arena = arena if arena is not None \
            else self.engine.arenas.get(type_name)
        if arena is None or arena.capacity != col.shape[0]:
            return  # keys unrecoverable; counts are lost (noted in stats)
        vals = np.asarray(jax.device_get(col))
        rows = np.nonzero(vals)[0]
        keys = arena._key_of_row[rows]
        live = keys >= 0
        retired = self._retired.setdefault(type_name, {})
        for k, v in zip(keys[live].tolist(), vals[rows[live]].tolist()):
            retired[k] = retired.get(k, 0) + int(v)
        self._retire_version += 1
        self._snap_cache = None

    def relocate(self) -> None:
        """Engine reshard: fold every arena's counts to host while the
        key→row mirrors still describe the old layout, and park the
        sketches/slot counters as host numpy — every device array here
        may be committed to the OLD device set (they ride fused-window
        outputs), and a mixed-device jit after a mesh change would
        reject them (ledger.relocate's reasoning).  The next fold
        re-uploads on the new device set; totals survive."""
        self.flush_folds()
        for name in list(self._counts):
            self.fold_type(name)
        for name, sk in list(self._cms.items()):
            if not isinstance(sk, np.ndarray):
                self._cms[name] = np.asarray(jax.device_get(sk))
        if self._slot_counts is not None \
                and not isinstance(self._slot_counts, np.ndarray):
            self._slot_counts = np.asarray(
                jax.device_get(self._slot_counts))
        # the delta plans and the stale counter are committed to the
        # old device set too; plans rebake from live batches, the
        # counter is advisory and restarts at zero
        self._plans = {}
        self._stale = None
        self._snap_cache = None

    # -- snapshots -----------------------------------------------------------

    def _confidence(self) -> float:
        return 1.0 - math.exp(-float(self.cms_depth))

    def snapshot(self, cache: bool = True) -> Dict[str, Any]:
        """The attribution snapshot: per-arena HotSet + skew gauges +
        per-method traffic, ONE batched ``device_get`` for all arenas'
        reduced outputs (d2h_fetches counts it; the transfer-budget test
        pins one per call).  ``cache=True`` reuses the last snapshot
        while no fold/retire has happened since — the load publisher's
        1s cadence must not turn snapshots into per-second device
        traffic on an idle silo."""
        self.flush_folds()
        key = (self.records, self._retire_version)
        if cache and self._snap_cache is not None \
                and self._snap_cache[0] == key:
            return self._snap_cache[1]
        pend: Dict[str, Any] = {}
        metas: Dict[str, Any] = {}
        for type_name, col in self._counts.items():
            arena = self.engine.arenas.get(type_name)
            if arena is None or arena.capacity != col.shape[0]:
                continue
            pend[type_name] = _snapshot_kernel(
                col, self.cms_for(type_name), self._seed_arr(),
                k=min(self.top_k, col.shape[0]), n_shards=arena.n_shards)
            metas[type_name] = arena
        if self._slot_counts is not None:
            pend["__slots__"] = self._slot_arr()
        if self._stale is not None:
            pend["__stale__"] = self._stale
        fetched = jax.device_get(pend) if pend else {}
        if pend:
            self.d2h_fetches += 1
        stale_now = int(fetched.get("__stale__", self._last_stale))
        if stale_now > self._last_stale:
            # checked applies mismatched since the last snapshot: the
            # baked plan content drifted from the live batches — drop
            # the plans so the next fold rebakes from current content
            self._plans.clear()
        self._last_stale = stale_now
        arenas: Dict[str, Any] = {}
        for type_name, arena in metas.items():
            vals, rows, shard, total, gini, p99, mean_nz, nnz, est = \
                fetched[type_name]
            retired = self._retired.get(type_name, {})
            cand: Dict[int, Dict[str, int]] = {}
            for v, r, e in zip(vals.tolist(), rows.tolist(), est.tolist()):
                if v <= 0:
                    continue
                k = int(arena._key_of_row[r])
                if k < 0:
                    continue  # freed between fold and snapshot
                cand[k] = {"msgs": int(v), "sketch": int(e)}
            # merge retired: candidates gain their retired history
            # (msgs AND sketch — the retired mirror is exact, so adding
            # it to the live-row CMS estimate keeps the published bound
            # one-sided even though the sketch hashed the OLD row); a
            # retired key that could displace the smallest candidate
            # joins (its live remainder cross-checked in one gather)
            for k, v in cand.items():
                if k in retired:
                    v["msgs"] += retired[k]
                    v["sketch"] += retired[k]
            if retired:
                # the floor only gates admission when the candidate set
                # is already full — with free top-K slots every retired
                # key joins (the evicted-but-hot grains are exactly the
                # ones an overloaded silo's rebalancer must see)
                floor = min((v["msgs"] for v in cand.values()), default=0) \
                    if len(cand) >= self.top_k else 0
                extra = [(k, c) for k, c in retired.items()
                         if k not in cand and c > floor]
                extra.sort(key=lambda kv: -kv[1])
                extra = extra[:self.top_k]
                if extra:
                    ekeys = np.asarray([k for k, _ in extra], np.int64)
                    erows, found = arena.lookup_rows(ekeys)
                    live_counts = np.zeros(len(extra), np.int64)
                    if found.any():
                        idx = _pow2_pad(
                            erows[found].astype(np.int32), 0)
                        live_counts[found] = np.asarray(_gather_counts(
                            self._counts[type_name],
                            jnp.asarray(idx)))[:int(found.sum())]
                        self.d2h_fetches += 1
                    for (k, c), lc in zip(extra, live_counts.tolist()):
                        cand[k] = {"msgs": int(c) + int(lc),
                                   "sketch": int(c) + int(lc)}
            retired_total = sum(retired.values())
            grand = int(total) + retired_total
            hot = sorted(cand.items(), key=lambda kv: -kv[1]["msgs"])
            hot = hot[:self.top_k]
            conf = self._confidence()
            # sketch_est clamps below at the exact count: a row move
            # (growth remap / compaction) strands the key's sketch
            # history in buckets hashed from the OLD row, so the raw
            # live-row estimate can undercount — the clamp keeps the
            # published one-sided bound true unconditionally
            hot_set = [{
                "key": k,
                "msgs": v["msgs"],
                "share": round(v["msgs"] / grand, 6) if grand else 0.0,
                "sketch_est": max(v["sketch"], v["msgs"]),
                "confidence": round(conf, 6),
            } for k, v in hot]
            shard_l = shard.tolist()
            arenas[type_name] = {
                "hot": hot_set,
                "total_msgs": grand,
                "live_msgs": int(total),
                "retired_msgs": retired_total,
                "topk_share": round(sum(h["msgs"] for h in hot_set)
                                    / grand, 6) if grand else 0.0,
                "skew": {
                    "max_shard_share": round(max(shard_l) / int(total), 6)
                    if int(total) else 0.0,
                    "gini": round(float(gini), 6),
                    "p99_to_mean": round(float(p99) / float(mean_nz), 4)
                    if float(mean_nz) else 0.0,
                    "hot_rows": int(nnz),
                },
                "shard_msgs": shard_l,
            }
        methods: Dict[str, int] = {}
        slots = fetched.get("__slots__")
        if slots is not None:
            for (t, m), s in self.slots.items():
                if int(slots[s]):
                    methods[f"{t}.{m}"] = int(slots[s])
        out = {
            "arenas": arenas,
            "methods": methods,
            "top_k": self.top_k,
            "sketch": {
                "depth": self.cms_depth,
                "width": self.cms_width,
                "epsilon": math.e / self.cms_width,
                "confidence": round(self._confidence(), 6),
            },
        }
        self._snap_cache = (key, out)
        return out

    def hot_set(self) -> List[Dict[str, Any]]:
        """The flattened HotSet contract for the load-publisher
        broadcast and the rebalancer: one entry per hot grain across all
        arenas, sorted by estimated message share."""
        if not self.enabled:
            return []
        snap = self.snapshot(cache=True)
        out = []
        for type_name, a in snap["arenas"].items():
            for h in a["hot"]:
                out.append({"arena": type_name, **h})
        out.sort(key=lambda h: -h["msgs"])
        return out[:self.top_k]

    def per_key_totals(self, type_name: str) -> Dict[int, int]:
        """EXACT per-grain totals, live + retired merged per key — the
        oracle-comparison surface (bench attribution tier, epoch
        bit-exactness tests).  Pays one full-column d2h; diagnostics
        only, never on the publish path."""
        self.flush_folds()
        out = {k: int(v)
               for k, v in self._retired.get(type_name, {}).items()}
        col = self._counts.get(type_name)
        arena = self.engine.arenas.get(type_name)
        if col is None or arena is None \
                or arena.capacity != col.shape[0]:
            return out
        vals = np.asarray(jax.device_get(col))
        self.d2h_fetches += 1
        rows = np.nonzero(vals)[0]
        keys = arena._key_of_row[rows]
        live = keys >= 0
        for k, v in zip(keys[live].tolist(), vals[rows[live]].tolist()):
            out[k] = out.get(k, 0) + int(v)
        return out

    def stats(self) -> Dict[str, Any]:
        """Cheap host-side plane health (no transfer)."""
        return {
            "enabled": self.enabled,
            "top_k": self.top_k,
            "cms_depth": self.cms_depth,
            "cms_width": self.cms_width,
            "tracked_arenas": len(self._counts),
            "records": self.records,
            "d2h_fetches": self.d2h_fetches,
            "retired_rows": self.retired_rows,
            "retired_keys": sum(len(d) for d in self._retired.values()),
            "plan_hits": self.plan_hits,
            "plan_checked": self.plan_checked,
            "plan_builds": self.plan_builds,
            "pending_folds": len(self._pending),
            "stale_folds": self._last_stale,
            "fold_compiles": fold_compiles(),
        }


def fold_compiles() -> int:
    """Compiled variants of the hot-path kernels (apply: one per
    accumulator layout; plan: one per batch shape ladder rung) — the
    compile-count half of the plane's cost contract, pinned by the
    budget test like the ledger's."""
    total = 0
    for kernel in (_apply_coalesced, _apply_checked_stack, _plan_kernel):
        size = getattr(kernel, "_cache_size", None)
        if size is None:
            continue
        try:
            total += int(size())
        except Exception:  # noqa: BLE001 — jax-version-specific API
            pass
    return total
