"""TensorEngine: the batched tick machine.

This is the rebuild's hot data plane, replacing the reference's per-message
Dispatcher/MessageCenter/Scheduler traversal (reference: Dispatcher.cs:38,
MessageCenter.cs:33, OrleansTaskScheduler.cs:37) with the north star's
tick pipeline:

    collect → resolve rows (directory) → apply batched kernels → route emits

A *tick* runs up to ``max_rounds_per_tick`` rounds so intra-tick call
chains (grain A's handler emitting to grain B) propagate without waiting
for the next tick — the batched analog of Orleans' continuation
interleaving (SURVEY.md §7 hard-part 2).  Messages still queued after the
round cap spill to the next tick.

Data-movement discipline (the design driver — measured on this platform,
d2h is orders of magnitude slower than device compute):

* host→device happens once per externally-injected batch (the client edge);
  ``BatchInjector`` amortizes even that by caching resolved destination
  rows for a stable key set.
* emit routing — the grain→grain hot path — never touches the host: each
  arena keeps a replicated device mirror of its key→row directory
  partition, and destinations resolve with a vectorized searchsorted
  *on the mesh*.  Only a scalar "unseen keys?" count crosses to the host
  per routed round, and only cold-start batches pay the (bounded,
  compacted) miss-key fetch that activates new rows.
* device→host happens only for explicitly requested results.
"""

from __future__ import annotations

import asyncio
import time
import weakref
from collections import defaultdict, deque
from dataclasses import dataclass, field as dc_field
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from orleans_tpu.config import (
    MetricsConfig,
    ProfilerConfig,
    TensorEngineConfig,
)
from orleans_tpu.core.grain import MethodInfo
from orleans_tpu.ids import GrainId
from orleans_tpu.tensor.arena import GrainArena
from orleans_tpu.tensor.attribution import WorkloadAttribution
from orleans_tpu.tensor.checkpoint import CheckpointPlane
from orleans_tpu.tensor.exchange import exchangeable_args
from orleans_tpu.tensor.ledger import DeviceLatencyLedger, SlotRegistry
from orleans_tpu.tensor.memledger import DeviceMemoryLedger
from orleans_tpu.tensor.profiler import (
    CAUSE_BUCKET_GROWTH,
    CAUSE_CONFIG_TOGGLE,
    CAUSE_CROSS_SHARD,
    CAUSE_GENERATION_REPACK,
    CAUSE_MESH_RESHARD,
    CAUSE_NEW_METHOD,
    CAUSE_SHAPE_CHANGE,
    CompileTracker,
    TickPhaseProfiler,
    host_read,
)
from orleans_tpu.tensor.vector_grain import (
    KEY_SENTINEL,
    Batch,
    Emit,
    VectorGrainInfo,
    ones_mask as _mask_for,
    vector_type,
)

# unique unseen keys activated per pass: a cold 1M-grain start needs
# ceil(1M / MISS_BUF) optimistic-miss cycles, each paying a device sort
# plus a completion observation.  Measured before PR 1 on a v5e reached
# over a network link (~100ms per completion observation): 2**17 cut
# the 1M-grain cold start 74s → 22s, while 2**20's bigger per-pass
# sort/pad cost more than the passes it saved.  On a local v5e, PR 21's
# chip_smoke.py cold-started 1M players in 9 passes: 91.4s with a cold
# compile cache, 14.7s with a warm one; 2**17 has not been re-tuned there
MISS_BUF = 1 << 17


@dataclass
class PendingBatch:
    """One queued slab of messages for a (type, method).

    Destination resolution precedence: ``rows`` when its ``generation``
    still matches the arena (injector fast path), else ``keys_host``
    (host resolution at dequeue), else ``keys_dev`` (device resolution —
    emits).  An injector batch carries all three: rows for the fast path,
    keys_host for re-resolution after repack, keys_dev so registered
    fan-outs expand with zero per-inject transfer.
    """

    args: Any                                  # pytree [m, ...] np or device
    rows: Optional[jnp.ndarray] = None         # int32[m] device
    keys_host: Optional[np.ndarray] = None     # int64[m]
    keys_dev: Optional[jnp.ndarray] = None     # int32[m] device
    # wide (64-bit) device keys as (hi, lo) int32 word pairs — resolved
    # through the arena's two-level hash/bucket mirror
    keys_wide: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
    mask: Optional[jnp.ndarray] = None         # bool[m] device (None = all)
    future: Optional[asyncio.Future] = None    # resolves to results[m]
    generation: int = -1                       # arena generation rows assume
    # arena eviction_epoch the rows assume: free-list deactivation frees
    # rows WITHOUT moving survivors (generation preserved), so cached
    # rows are valid only while both match — an epoch-only mismatch
    # falls back to host re-resolution (which re-activates evicted keys)
    epoch: int = -1
    # miss-check redeliveries set this: the original pass already expanded
    # the whole batch through any registered fan-out (expansion is
    # key-based, not row-based), so expanding again would double-deliver
    no_fanout: bool = False
    # tracing: the trace context of the request that enqueued this batch
    # (host-path bridged calls only — captured from the ambient
    # RequestContext at enqueue).  The executing tick links its BATCHED
    # span back to this trace; never one span per message
    trace: Optional[Dict[str, Any]] = None
    # device latency ledger (tensor/ledger.py): the engine tick at which
    # this batch was injected/emitted.  The executing tick's delta to it
    # is the batch's turn latency in device ticks; -1 = unstamped (not
    # counted).  Miss-path redeliveries carry the ORIGINAL stamp so the
    # recorded latency includes the redelivery wait.
    inject_tick: int = -1
    # pull-mode delivery (tensor/streams_plane.py): row-aligned edge
    # offsets int32[capacity + 1] — lanes are grouped by destination
    # arena row, ``rows`` carries the per-edge destination rows, and
    # the step's segment reductions run scatter-free.  Valid only while
    # (generation, epoch) still match the arena; a stale batch falls
    # back to key-addressed delivery through ``keys_dev``.
    segments: Optional[jnp.ndarray] = None
    # cross-shard exchange overlap (tensor/exchange.py): the round-start
    # pre-dispatch pass stores (rows2, args2, mask2, dropped, stats,
    # generation, epoch, rows_identity, t_dispatch) here so the
    # all_to_all of this batch runs under the PRECEDING groups' compute;
    # _run_group consumes it only when the stamps and the resolved rows
    # identity still match (a stale pre-exchange is silently recomputed)
    pre_exchange: Optional[Tuple] = None

    def __len__(self) -> int:
        for c in (self.rows, self.keys_host, self.keys_dev):
            if c is not None:
                return len(c)
        if self.keys_wide is not None:
            return len(self.keys_wide[0])
        raise ValueError("empty batch")


@dataclass
class _MissCheck:
    """A parked optimistic-resolution check (see _resolve_batch)."""

    arena: Any
    type_name: str
    method: str
    keys: jnp.ndarray
    valid: jnp.ndarray
    rows: jnp.ndarray
    miss_count: jnp.ndarray
    args: Any
    inject_tick: int = -1  # original ledger stamp, carried to redelivery


@dataclass
class _FanoutCheck:
    """A parked fan-out/subscription expansion overflow check: source
    lanes whose ragged expansion did not fit the CSR width delivered
    NOTHING this round (all-or-nothing per lane) and carry a device-side
    dropped mask; at the next quiescence point the engine re-expands
    exactly those lanes and their subscriber deliveries enqueue with the
    ORIGINAL ``inject_tick`` (never silent loss, never a mid-tick error
    — the ShardExchange contract, replacing FanoutOverflowError)."""

    expander: Any              # DeviceFanout | DeviceSubscriptions
    dst_type: str
    dst_method: str
    keys: jnp.ndarray          # int32[m] device — source keys
    args: Any                  # the source args pytree
    dropped: jnp.ndarray       # bool[m] device — parked source lanes
    count: jnp.ndarray         # int32 device scalar
    inject_tick: int = -1


@dataclass
class _ExchangeCheck:
    """A parked cross-shard exchange overflow check (tensor/exchange.py):
    lanes that did not fit their destination bucket carry a device-side
    dropped mask; at the next quiescence point they re-deliver through
    the same path with the ORIGINAL inject stamp (never silent loss,
    same discipline as _MissCheck)."""

    type_name: str
    method: str
    keys: Optional[jnp.ndarray]  # int32[m] device — redelivery addresses
    args: Any                    # the PRE-exchange args pytree
    dropped: Optional[jnp.ndarray]  # bool[m] device
    # int32[3 + 2·n_shards] device: (cross, dropped, delivered) sums
    # plus the per-destination bucket demand the occupancy estimator
    # feeds on, max-over-sources then sum-over-sources (legacy [3 + n]
    # checks from older paths still drain — fold_stats is
    # width-agnostic)
    stats: jnp.ndarray
    # lanes of the exchanged batch: the demand tail is read per valid
    # lane of it
    width: int
    inject_tick: int = -1
    # a disengaged-exchange probe: stats fold at drain, but the batch
    # delivered through the normal path — NOTHING may redeliver
    measure_only: bool = False
    # probe sampling factor: the probe runs on 1-in-N eligible groups,
    # so its COUNT stats scale by N at fold time to stay an unbiased
    # estimate comparable with engaged-mode exact totals (the demand
    # tail is a per-drain peak, never scaled)
    scale: int = 1


@jax.jit
def _resolve_rows_kernel(sorted_keys, sorted_rows, keys, valid):
    """Device-side directory lookup: keys → rows (-1 = unseen).

    The batched analog of LocalGrainDirectory lookup (reference:
    LocalGrainDirectory.cs:34): the sorted index IS this type's directory
    partition, replicated across the mesh."""
    n = sorted_keys.shape[0]
    valid = valid & (keys < KEY_SENTINEL)
    idx = jnp.clip(jnp.searchsorted(sorted_keys, keys), 0, n - 1)
    hit = (sorted_keys[idx] == keys) & valid
    rows = jnp.where(hit, sorted_rows[idx], -1)
    return rows, jnp.sum(hit ^ valid)  # miss count


@jax.jit
def _resolve_rows_dense_kernel(dense, keys, valid):
    """Dense directory lookup: one gather instead of a binary search —
    measured ~80x cheaper at 1M messages (the searchsorted path costs
    ~80ms/tick on TPU; a gather ~1ms)."""
    size = dense.shape[0]
    # sentinel contract parity with the sorted kernel: keys >= sentinel
    # are padding, never misses
    valid = valid & (keys < KEY_SENTINEL)
    in_range = valid & (keys >= 0) & (keys < size)
    rows = jnp.where(in_range,
                     dense[jnp.clip(keys, 0, size - 1)], -1)
    hit = in_range & (rows >= 0)
    return rows, jnp.sum(hit ^ valid)  # miss count


def _mix32_dev(hi, lo):
    """Device twin of arena.mix32_np — MUST stay bit-identical."""
    h = (hi.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)) \
        ^ (lo.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35))
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x27D4EB2F)
    h = h ^ (h >> 13)
    return (h & jnp.uint32(0x3FFFFFFF)).astype(jnp.int32)


#: bucket-collision probe depth: a run of >4 equal 30-bit hashes among
#: live keys is astronomically unlikely; keys that still miss fall back
#: to exact host-path redelivery (never silent loss, never a device loop)
WIDE_PROBES = 4


@jax.jit
def _resolve_rows_wide_kernel(sorted_h, rows_by_h, hi_col, lo_col,
                              hi, lo, valid):
    """Two-level wide-key directory lookup: 30-bit bucket searchsorted,
    then candidate rows verified against the full key words (the device
    mirror for keys wider than int32; reference: UniqueKey.cs:34)."""
    h = _mix32_dev(hi, lo)
    n = sorted_h.shape[0]
    cap = hi_col.shape[0]
    idx = jnp.clip(jnp.searchsorted(sorted_h, h), 0, n - 1)
    rows = jnp.full(h.shape, -1, jnp.int32)
    for k in range(WIDE_PROBES):
        j = jnp.clip(idx + k, 0, n - 1)
        cand = rows_by_h[j]
        cr = jnp.clip(cand, 0, cap - 1)
        # `valid` folds into the returned rows — same invariant as the
        # narrow kernels (downstream consumers mask on rows >= 0)
        ok = valid & (sorted_h[j] == h) & (cand >= 0) \
            & (hi_col[cr] == hi) & (lo_col[cr] == lo)
        rows = jnp.where((rows < 0) & ok, cand, rows)
    hit = (rows >= 0) & valid
    return rows, jnp.sum(hit ^ valid)


def resolve_rows_on_device(arena, keys, valid):
    """Pick the cheapest device resolve for this arena: dense direct-map
    when the key space affords it, else sorted searchsorted; wide keys
    (an ``(hi, lo)`` int32 word pair) and arenas holding wide keys use
    the two-level hash/bucket mirror.  Arenas holding hot-grain replicas
    pay one extra spread step: lanes resolving to a replicated primary
    re-point to a replica row by lane hash (the mirror is row-keyed, so
    the spread composes with every key-width path)."""
    if isinstance(keys, tuple):
        hi, lo = keys
        rows, misses = _resolve_rows_wide_kernel(
            *arena.device_index_wide(), hi, lo, valid)
        return _spread_resolved(arena, rows), misses
    if arena.has_wide_keys:
        # narrow emit keys into a wide-keyed arena: the narrow mirror
        # cannot exist (it would overflow); route through the wide one
        # (an int32 emit key k is the wide key (0, k)).  Sentinel-parity
        # with the narrow kernels: keys >= KEY_SENTINEL are padding,
        # never lookups — without this a padding lane (0, 2**31-1) could
        # alias a live grain whose key IS 2**31-1
        valid = valid & (keys < KEY_SENTINEL)
        rows, misses = _resolve_rows_wide_kernel(
            *arena.device_index_wide(), jnp.zeros_like(keys), keys, valid)
        return _spread_resolved(arena, rows), misses
    dense = arena.dense_index()
    if dense is not None:
        rows, misses = _resolve_rows_dense_kernel(dense, keys, valid)
    else:
        sk, sr = arena.device_index()
        rows, misses = _resolve_rows_kernel(sk, sr, keys, valid)
    return _spread_resolved(arena, rows), misses


def _spread_resolved(arena, rows):
    """Apply the hot-grain replica spread when the arena has promoted
    grains (tensor/arena.py: the mirror arrays are runtime jit INPUTS,
    not baked constants — a promote/demote re-runs nothing, the next
    dispatch just reads the new table)."""
    if not arena._replicas:
        return rows
    from orleans_tpu.tensor.arena import _spread_replicas_kernel
    return _spread_replicas_kernel(*arena.replica_mirror(), rows)


@partial(jax.jit, static_argnames=("miss_buf",))
def _miss_keys_kernel(keys, rows, valid, miss_buf: int):
    """Compact the unseen keys (cold path only — involves a device sort)."""
    missing = (rows < 0) & valid & (keys < KEY_SENTINEL)
    return jnp.unique(jnp.where(missing, keys, KEY_SENTINEL),
                      size=miss_buf, fill_value=KEY_SENTINEL), missing


def _fence_block(fence) -> None:
    """Executor-thread completion wait on a tick's FENCE output (a
    1-lane array no program ever donates).  Blocking here converts the
    device's completion signal into an asyncio future resolution — the
    event-driven observation path; the dispatch path never blocks."""
    try:
        jax.block_until_ready(fence)
    except RuntimeError as e:
        # a DELETED fence can only mean a LATER program consumed the
        # buffer — engine fences are never donated, so this covers
        # exotic caller-supplied tokens; the work it fenced is done.
        # Anything else (XlaRuntimeError subclasses RuntimeError: OOM,
        # execution failure) is a real device failure and must surface
        # through the completion future, never read as a completed tick
        if "deleted" not in str(e).lower():
            raise


class TickPipeline:
    """Continuous pipelined ticking: event-driven completion tracking
    plus depth-bounded backpressure.

    Every dispatched tick registers a completion future on its device
    fence; an executor thread resolves it the moment the device
    signals.  The engine loop (and the bench latency rig) lets up to
    ``config.pipeline_depth`` ticks ride in flight before awaiting the
    OLDEST completion, so tick N+1's dispatch — and its staged h2d
    injection — overlaps tick N's device execution.  Donated state
    buffers (``config.donate_state``) make the overlap safe: XLA
    double-buffers the arena columns in place, and no host round-trip
    ever serializes back-to-back ticks.

    ``overlap_seconds`` accrues the device time that ran concurrently
    with later host work (completion timestamp minus dispatch-return
    timestamp) — the profiler's phase-reconciliation credit: pipelined
    phases overlap, so host-side phase sums no longer tile wall time."""

    def __init__(self, engine: "TensorEngine") -> None:
        self.engine = engine
        self._inflight: deque = deque()  # (tick, dispatched_at, future)
        self.ticks_tracked = 0
        self.completions = 0
        self.waits = 0
        self.wait_seconds = 0.0
        self.overlap_seconds = 0.0
        self.max_inflight = 0
        self._tick_overlap = 0.0

    @property
    def depth(self) -> int:
        return max(1, int(self.engine.config.pipeline_depth))

    def inflight(self) -> int:
        self._prune()
        return len(self._inflight)

    def _prune(self) -> int:
        q = self._inflight
        while q and q[0][2].done():
            q.popleft()
        return len(q)

    def note_tick(self, fence, on_complete=None):
        """Register completion tracking for the tick that just
        dispatched ``fence``; returns the completion future (None when
        nothing was registered).  No-op outside a running event loop
        (sync drivers have nothing to resolve the future into).
        ``on_complete(timestamp)``, when given, runs IN the executor
        thread the moment the fence resolves — rigs timestamp the
        device event there instead of blocking a SECOND thread on the
        same fence."""
        if fence is None:
            return None
        if on_complete is None:
            work = partial(_fence_block, fence)
        else:
            def work(f=fence, cb=on_complete):
                _fence_block(f)
                cb(time.perf_counter())
        try:
            loop = asyncio.get_running_loop()
            fut = loop.run_in_executor(None, work)
        except RuntimeError:
            return None  # no loop, or executor already shut down
        dispatched = time.perf_counter()
        self.ticks_tracked += 1

        def _completed(_f, t0=dispatched) -> None:
            self.completions += 1
            overlap = max(0.0, time.perf_counter() - t0)
            self.overlap_seconds += overlap
            self._tick_overlap += overlap

        fut.add_done_callback(_completed)
        self._inflight.append((self.engine.tick_number, dispatched, fut))
        self.max_inflight = max(self.max_inflight, len(self._inflight))
        return fut

    def take_tick_overlap(self) -> float:
        """Overlap credit accrued since the last tick observed it
        (consumed by the profiler's reconciliation)."""
        o, self._tick_overlap = self._tick_overlap, 0.0
        return o

    async def throttle(self) -> None:
        """Backpressure: await oldest completions until fewer than
        ``depth`` ticks are in flight.  This is the pipeline's only
        wait, and it is an EVENT (the fence future), not a poll."""
        while self._prune() >= self.depth:
            fut = self._inflight[0][2]
            t0 = time.perf_counter()
            await fut
            self.waits += 1
            self.wait_seconds += time.perf_counter() - t0

    async def drain(self) -> None:
        """Quiesce: await every in-flight completion."""
        while self._prune():
            await self._inflight[0][2]

    def snapshot(self) -> Dict[str, Any]:
        return {
            "depth": self.depth,
            "inflight": self.inflight(),
            "ticks_tracked": self.ticks_tracked,
            "completions": self.completions,
            "waits": self.waits,
            "wait_seconds": round(self.wait_seconds, 6),
            "overlap_seconds": round(self.overlap_seconds, 6),
            "max_inflight": self.max_inflight,
            "donation_fallbacks": self.engine.donation_fallbacks,
        }


@jax.jit
def _stack_counts(*xs):
    """Gather N parked miss counters into ONE buffer: reading them one
    int() at a time costs one completion observation EACH (~100ms each
    on the pre-PR-1 chip rig, where it was measured as THE dominant
    unfused-tier cost); stacked, the whole drain pays one."""
    return jnp.stack(xs)


class IncrementalCollector:
    """Chunked, tick-interleaved activation collection with a bounded
    pause budget — the tensor-path realization of the reference
    collector's central property: deactivation is a BACKGROUND cost,
    never a message-pump stall (reference: ActivationCollector.cs:37,
    Catalog.cs:836).

    A *sweep* selects every arena's idle victims once (on device — one
    vectorized compare, only the victim mask crosses to the host) and
    parks them as a work list.  *Slices* then drain the list in
    ``collection_chunk_rows`` chunks between ticks, each slice capped at
    ``collection_pause_budget_s`` of host wall time; each chunk
    re-validates liveness/idleness before freeing, so rows touched since
    selection are spared.  Victims are freed only after their columnar
    write-back acks — an injected storage fault leaves them live for the
    retry (next slice re-attempts; a synchronous drain propagates).
    """

    def __init__(self, engine: "TensorEngine") -> None:
        self.engine = engine
        # work list: [arena, cutoff, write_back, generation, rows]
        self._pending: deque = deque()
        self.sweeps_started = 0
        self.sweeps_completed = 0
        self.slices_run = 0
        self.rows_evicted = 0
        self.victims_dropped_stale = 0  # generation moved mid-sweep
        self.write_back_failures = 0
        self._last_write_error: Optional[BaseException] = None
        # recent slice records: telemetry + the flight-recorder dump
        self.last_slices: deque = deque(maxlen=64)
        self.pause_seconds: deque = deque(maxlen=256)
        self.max_pause_s = 0.0

    def active(self) -> bool:
        return bool(self._pending)

    def pending_rows(self) -> int:
        return sum(len(e[4]) for e in self._pending)

    def start_sweep(self, cutoff: int, write_back: bool = True) -> int:
        """Select victims across all arenas (device compare, mask-only
        transfer) and park them for sliced draining.  No-op while a
        previous sweep is still draining.  Returns rows selected."""
        if self._pending:
            return 0
        selected = 0
        for arena in self.engine.arenas.values():
            victims = arena.select_idle_rows(cutoff)
            if len(victims):
                self._pending.append(
                    [arena, cutoff, write_back, arena.generation, victims])
                selected += len(victims)
        if selected:
            self.sweeps_started += 1
        return selected

    def run_slice(self, budget_s: float, chunk_rows: int) -> int:
        """Drain chunks until the pause budget is spent or the sweep is
        done.  ``budget_s <= 0`` = unbounded (the synchronous baseline).
        Returns rows evicted this slice."""
        if not self._pending:
            return 0
        t0 = time.perf_counter()
        chunk_rows = max(1, int(chunk_rows))
        freed = 0
        failed = False
        while self._pending:
            entry = self._pending[0]
            arena, cutoff, write_back, gen, rows = entry
            if arena.generation != gen:
                # rows moved since selection (grow/reshard/threshold
                # compaction): the ids are meaningless now — drop the
                # remainder (counted); the next cadence sweep (or the
                # explicit collect_idle re-sweep loop) re-selects
                self.victims_dropped_stale += len(rows)
                self._pending.popleft()
                continue
            chunk, entry[4] = rows[:chunk_rows], rows[chunk_rows:]
            if len(entry[4]) == 0:
                self._pending.popleft()
            else:
                self._pending[0] = entry
            try:
                freed += arena.deactivate_idle_rows(chunk, cutoff,
                                                    write_back)
            except Exception as exc:  # noqa: BLE001 — storage faults
                # (chaos seam included) must not kill the tick loop:
                # nothing in this chunk was freed (write-back precedes
                # freeing) — park it back at the FRONT and retry next
                # slice; a synchronous drain() propagates instead
                self.write_back_failures += 1
                self._last_write_error = exc
                if len(entry[4]):
                    entry[4] = np.concatenate([chunk, entry[4]])
                    self._pending[0] = entry
                else:
                    entry[4] = chunk
                    self._pending.appendleft(entry)
                failed = True
                break
            if budget_s > 0 and time.perf_counter() - t0 >= budget_s:
                break
        dt = time.perf_counter() - t0
        self.slices_run += 1
        self.rows_evicted += freed
        self.pause_seconds.append(dt)
        self.max_pause_s = max(self.max_pause_s, dt)
        done = not self._pending
        if done:
            self.sweeps_completed += 1
        self._record_slice(dt, freed, done, failed)
        return freed

    def drain(self, chunk_rows: int) -> int:
        """Synchronously finish the in-progress sweep (explicit
        ``collect_idle`` and quiesce points).  A write-back failure
        propagates here — silent infinite retry is a tick-loop luxury."""
        total = 0
        while self._pending:
            before = self.write_back_failures
            total += self.run_slice(0.0, chunk_rows)
            if self.write_back_failures > before:
                raise self._last_write_error
        return total

    def _record_slice(self, dt: float, freed: int, done: bool,
                      failed: bool) -> None:
        engine = self.engine
        record = {
            "tick": engine.tick_number,
            "seconds": round(dt, 6),
            "evicted": freed,
            "remaining": self.pending_rows(),
            "sweep_done": done,
            "write_back_failed": failed,
        }
        self.last_slices.append(record)
        rec = engine._span_recorder()
        if rec is not None:
            rec.collect_span(tick=engine.tick_number, duration=dt,
                             evicted=freed,
                             remaining=record["remaining"],
                             sweep_done=done, failed=failed)
        silo = engine.silo
        reg = getattr(silo, "metrics_registry", None) \
            if silo is not None else None
        if reg is not None:
            # typed registry (orleans_tpu/metrics.py): the live per-slice
            # pause histogram — the periodic collect_metrics rollup
            # mirrors the p99/max gauges from the same data
            reg.histogram("collect.pause_s", base=1e-6).observe(dt)
        from orleans_tpu import telemetry
        mgr = telemetry.default_manager
        if mgr.consumers:
            mgr.track_metric("collect.pause_s", dt)
            if done:
                for name, arena in engine.arenas.items():
                    mgr.track_metric("arena.fragmentation",
                                     arena.fragmentation(),
                                     {"arena": name})

    def snapshot(self) -> Dict[str, Any]:
        return {
            "sweeps_started": self.sweeps_started,
            "sweeps_completed": self.sweeps_completed,
            "slices_run": self.slices_run,
            "rows_evicted": self.rows_evicted,
            "victims_dropped_stale": self.victims_dropped_stale,
            "pending_rows": self.pending_rows(),
            "write_back_failures": self.write_back_failures,
            "pause_p99_s": self.pause_p99_s(),
            "max_pause_s": self.max_pause_s,
            "last_slices": list(self.last_slices),
        }

    def pause_p99_s(self) -> float:
        """p99 over the recent slice pauses (cheap enough for periodic
        telemetry publication without building a full snapshot)."""
        if not self.pause_seconds:
            return 0.0
        return float(np.percentile(np.asarray(self.pause_seconds), 99))


class TensorEngine:

    def __init__(self, silo=None, config: Optional[TensorEngineConfig] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 initial_capacity: int = 1024,
                 store: Optional[Any] = None,
                 metrics: Optional[MetricsConfig] = None,
                 profiler: Optional[ProfilerConfig] = None,
                 snapshot_store: Optional[Any] = None) -> None:
        self.silo = silo
        self.config = config or TensorEngineConfig()
        # on-device latency ledger (tensor/ledger.py): per-(type, method)
        # log2 histograms of inject→completion tick deltas, accumulated
        # inside the tick; MetricsConfig.ledger_enabled gates it live
        self.metrics_config = metrics or MetricsConfig()
        # shared (type, method) → slot map: the ledger's histogram rows
        # and the attribution plane's traffic counters index identically
        self.slot_registry = SlotRegistry()
        self.ledger = DeviceLatencyLedger(
            n_buckets=self.metrics_config.ledger_buckets,
            enabled=(self.metrics_config.enabled
                     and self.metrics_config.ledger_enabled),
            slots=self.slot_registry)
        # workload attribution plane (tensor/attribution.py): per-row
        # traffic counts + count-min sketch + skew gauges, accumulated
        # in the dispatch phase and threaded through fused windows like
        # the ledger hist
        self.attribution = WorkloadAttribution(
            self,
            enabled=(self.metrics_config.enabled
                     and self.metrics_config.attribution_enabled),
            top_k=self.metrics_config.attribution_top_k,
            cms_depth=self.metrics_config.attribution_cms_depth,
            cms_width=self.metrics_config.attribution_cms_width,
            slots=self.slot_registry)
        # the device cost plane (tensor/profiler.py + memledger.py):
        # tick-phase attribution + triggered deep capture, cause-coded
        # compile accounting, and HBM-by-owner accounting
        self.profiler = TickPhaseProfiler(self, profiler)
        self.compile_tracker = CompileTracker()
        self.memledger = DeviceMemoryLedger(self)
        self.mesh = mesh
        self.initial_capacity = initial_capacity
        # VectorStore backing every arena (tensor/persistence.py):
        # activation reads, eviction write-back, checkpoints
        self.store = store
        self._apply_mesh(mesh)

        self.arenas: Dict[str, GrainArena] = {}
        # incremental activation collection: sweeps select on device,
        # slices drain between ticks under the configured pause budget
        self.collector = IncrementalCollector(self)
        self.queues: Dict[Tuple[str, str], List[PendingBatch]] = defaultdict(list)
        self.tick_number = 0
        self.ticks_run = 0
        self.rounds_run = 0
        self._last_checkpoint_tick = 0
        self.messages_processed = 0
        self.tick_seconds = 0.0
        self.activation_passes = 0
        # recent per-tick durations → honest latency percentiles; the
        # adaptive controller (SURVEY §7 hard-part 5) reads the same data
        self.tick_durations: deque = deque(maxlen=self.config.latency_window)
        self._adaptive_interval = self.config.tick_interval
        # per-stage host wall time (the StageAnalysis analog, reference:
        # src/Orleans/Statistics/StageAnalysis.cs:81) lives on the
        # profiler's stages (profiler.stage_seconds / tick_stages), so a
        # slow tick can name its slow stage.  Device work is
        # async-dispatched; a stage's time is its host-side cost plus any
        # device sync its data dependencies force.

        self._step_cache: Dict[Tuple[str, str, int], Callable] = {}
        # compile-churn attribution (tensor/profiler.py): step-call input
        # signatures already paid for ((type, method, is_host, m)); the
        # first call of a new signature is timed and cause-coded.  A
        # reshard drops the compiled steps — signatures it forgot are
        # re-attributed to the reshard, not to "new" traffic.
        self._seen_steps: set = set()
        self._reshard_forgotten: set = set()
        # a live donate_state toggle equally drops the compiled steps;
        # its forgotten signatures re-attribute to the toggle
        self._toggle_forgotten: set = set()
        self._steps_donated = self.config.donate_state
        self.reshard_count = 0
        # continuous pipelined ticking: event-driven completion tracking
        # + depth backpressure; the fence is the latest tick's 1-lane
        # completion output (never donated — see _get_step)
        self.pipeline = TickPipeline(self)
        self._tick_fence = None
        # executions that fell back to the undonated path (donate_state
        # off): the pipeline still works, but XLA can no longer
        # double-buffer state in place
        self.donation_fallbacks = 0
        # live migration accounting (migrate_keys): batched move
        # operations and grains moved — the rebalance controller's
        # actuator counters, published as rebalance.* by the silo
        self.migrations = 0
        self.grains_migrated = 0
        # hot-grain replication accounting (replicate_key/demote_key):
        # the rebalance controller's second actuator, published as
        # rebalance.replicated/demoted/replica_folds by the silo
        self.replications = 0
        self.grains_replicated = 0
        self.replica_demotions = 0
        self._pending_checks: List[_MissCheck] = []
        # parked cross-shard exchange overflow checks (drained with the
        # miss checks — one batched device read covers both families)
        self._exchange_checks: List[_ExchangeCheck] = []
        # batches parked by the handoff fence during a tick's rounds;
        # re-queued at tick end so they retry next tick, not next round
        self._fence_deferred: List[Tuple[Tuple[str, str], PendingBatch]] = []
        # the durable state plane (tensor/checkpoint.py): full-arena
        # columnar checkpoints pinned at tick boundaries + device
        # journal + crash recovery.  Engaged by attaching a
        # SnapshotStore (constructor or checkpointer.attach_store);
        # _journal_sites is the O(1) ingress-hook predicate.
        self.checkpointer = CheckpointPlane(self, snapshot_store)
        self._journal_sites: set = set()
        # cross-silo slab router (tensor/router.py); attached by the silo
        # in cluster mode.  When set, batch entry points partition keys by
        # ring owner and only locally-owned keys ever activate here
        # (single-activation enforcement, reference: Catalog.cs:533-563)
        self.router = None
        # steady-state detector + transparent window compiler
        # (tensor/autofuse.py)
        from orleans_tpu.tensor.autofuse import AutoFuser
        self.autofuser = AutoFuser(self)
        # (src_type, src_method) → (DeviceFanout, dst_type, dst_method):
        # one-to-many subscription expansion on the device (tensor/fanout.py)
        self._fanouts: Dict[Tuple[str, str], Tuple[Any, str, str]] = {}
        # (src_type, src_method) → DeviceSubscriptions — the streams
        # plane (tensor/streams_plane.py): stream-ingress messages fan
        # out to the streams' subscribers, pull-mode when the publish
        # pattern matches the bound key set, push-mode otherwise
        self._stream_routes: Dict[Tuple[str, str], Any] = {}
        # device timers plane (tensor/timers_plane.py): hierarchical
        # timing wheel over per-type slot columns, harvested each tick
        # into batched receive_reminder calls.  Always constructed —
        # config.timers_plane gates the run_tick harvest only, so armed
        # state survives a live toggle
        from orleans_tpu.tensor.timers_plane import TimersPlane
        self.timers = TimersPlane(self)
        # parked fan-out/subscription overflow checks (drained with the
        # miss checks — one batched device read covers the family)
        self._fanout_checks: List[_FanoutCheck] = []
        self._task: Optional[asyncio.Task] = None
        self._running = False
        self._wake: Optional[asyncio.Event] = None
        # tracing (orleans_tpu/spans.py): per-tick accumulators for the
        # BATCHED tick span — distinct request traces executed this tick
        # and per-(type, method) message counts
        self._tick_traces: List[Dict[str, Any]] = []
        self._tick_counts: Dict[str, int] = defaultdict(int)

    def _span_recorder(self):
        """The owning silo's SpanRecorder when tracing is on; None for
        standalone engines (benches) or tracing disabled — every tracing
        hook gates on this so the hot path pays one attribute check."""
        silo = self.silo
        if silo is None:
            return None
        rec = getattr(silo, "spans", None)
        return rec if rec is not None and rec.enabled else None

    def _apply_mesh(self, mesh: Optional[jax.sharding.Mesh]) -> None:
        self.mesh = mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self.n_shards = mesh.devices.size
            self.sharding = NamedSharding(mesh,
                                          PartitionSpec(self.config.mesh_axis))
            self.replicated = NamedSharding(mesh, PartitionSpec())
        else:
            self.n_shards = 1
            self.sharding = None
            self.replicated = None
        # device-resident cross-shard router (tensor/exchange.py): built
        # whenever a multi-shard mesh is present so the
        # config.cross_shard_exchange toggle can flip live; counters
        # carry across a reshard (the compiled programs do not — they
        # specialize on the shard layout)
        prev = getattr(self, "exchange", None)
        if mesh is not None and self.n_shards > 1:
            from orleans_tpu.tensor.exchange import ShardExchange
            self.exchange = ShardExchange(self)
            self.exchange.adopt_stats(prev)
        else:
            self.exchange = None

    def _exchange_live(self) -> bool:
        """True when device batches route through the cross-shard
        exchange (mesh present + config toggle on) — the one predicate
        the unfused dispatch, the fused trace, and prepare()'s re-trace
        detection all share."""
        return self.exchange is not None and \
            self.config.cross_shard_exchange

    def _streams_live(self) -> bool:
        """True when stream-subscription routes expand on device
        (config.tensor.stream_plane) — the one predicate the unfused
        dispatch, the fused trace, and prepare()'s re-trace detection
        share.  Off = the host-expansion baseline the streams bench
        A/Bs against (a live toggle re-traces, cause config_toggle)."""
        return bool(self.config.stream_plane)

    def _stream_routes_signature(self) -> Tuple:
        """Fused-window re-trace input: registered routes + their
        adjacency layout versions (a rebuild re-bakes the windows' CSR
        trace constants)."""
        return tuple((key, id(r), r.layout_version, r.mutation_version)
                     for key, r in sorted(self._stream_routes.items()))

    # ================= arenas =============================================

    def arena_for(self, type_name: str) -> GrainArena:
        arena = self.arenas.get(type_name)
        if arena is None:
            info = vector_type(type_name)
            if info is None:
                raise KeyError(f"{type_name!r} is not a @vector_grain type")
            arena = GrainArena(info, capacity=self.initial_capacity,
                               n_shards=self.n_shards, sharding=self.sharding,
                               store=self.store)
            arena.compact_fragmentation = \
                self.config.compact_fragmentation_threshold
            # row moves (growth/compaction/reshard) must settle this
            # engine's auto-fusion chain FIRST — see
            # GrainArena._settle_owner_chain
            arena._owner_engine = weakref.ref(self)
            self.arenas[type_name] = arena
        return arena

    # ================= collection / elasticity / checkpoint ===============

    def collect_idle(self, max_idle_ticks: int,
                     write_back: bool = True) -> int:
        """Deactivate rows idle for > max_idle_ticks across all arenas
        (the age-based collector sweep, reference:
        ActivationCollector.cs:37) and return the count — the explicit,
        run-to-completion entry point (tests, management RPC, quiesce).
        Any in-progress incremental sweep drains first; the tick loop
        instead drains the same pipeline in pause-budgeted slices."""
        chunk = self.config.collection_chunk_rows
        self.collector.drain(chunk)
        cutoff = self.tick_number - max_idle_ticks
        total = 0
        while True:
            # re-sweep until nothing is selected: a mid-drain threshold
            # compaction bumps the generation and drops that sweep's
            # remaining victim ids — the explicit API must still run to
            # completion, so the survivors are re-selected (they are
            # still idle; compaction preserves last-use)
            if self.collector.start_sweep(cutoff,
                                          write_back=write_back) == 0:
                return total
            evicted = self.collector.drain(chunk)
            total += evicted
            if evicted == 0:
                return total

    def migrate_keys(self, type_name: str, keys: np.ndarray,
                     dst_shards, pin: bool = True) -> int:
        """Batched live migration of grains between device-shard blocks
        (the rebalance controller's actuator — runtime/rebalancer.py):
        one columnar gather/scatter moves k grains' rows, the eviction
        epoch bumps so in-flight resolved batches re-validate, and the
        move is pinned so evict→reactivate cycles honor it
        (arena.migrate_keys).  Parked optimistic checks drain FIRST:
        their redeliveries re-resolve against the post-move index, the
        same at-least-once net every row-lifecycle event rides.
        Returns grains actually moved."""
        arena = self.arenas.get(type_name)
        if arena is None:
            return 0
        if self._pending_checks or self._exchange_checks \
                or self._fanout_checks:
            self._drain_checks()
        t_mv0 = time.perf_counter()
        moved = arena.migrate_keys(keys, dst_shards, pin=pin)
        if moved:
            self.migrations += 1
            self.grains_migrated += moved
            rec = self._span_recorder()
            if rec is not None:
                # migration-wave episode: plan→move→adopt collapses
                # into one device gather/scatter here; rows moved is
                # the plane counter the timeline annotates
                rec.plane_span("migration", f"wave {type_name}",
                               duration=time.perf_counter() - t_mv0,
                               rows_moved=moved, tick=self.tick_number,
                               type=type_name)
        return moved

    def replicate_key(self, type_name: str, key: int, k: int) -> int:
        """Promote one hot grain to ``k`` replica rows spread over
        shards (the rebalance controller's second actuator — for grains
        too hot for ANY single shard, where migration just moves the
        burn).  Delivery scatters across the replicas by lane hash, so
        the per-pair exchange demand divides by k; reads and checkpoints
        observe the commutative fold (arena.promote_replicas).  Parked
        optimistic checks drain FIRST, the migrate_keys discipline:
        their redeliveries re-resolve (and re-spread) against the
        post-promotion table.  Returns the replica group size (0 if the
        type is unknown)."""
        arena = self.arenas.get(type_name)
        if arena is None:
            return 0
        if self._pending_checks or self._exchange_checks \
                or self._fanout_checks:
            self._drain_checks()
        if int(key) in arena._replicas:
            return len(arena._replicas[int(key)])
        got = arena.promote_replicas(key, k)
        self.replications += 1
        self.grains_replicated += 1
        rec = self._span_recorder()
        if rec is not None:
            rec.plane_span("migration", f"replicate {type_name}",
                           key=int(key), replicas=got,
                           tick=self.tick_number)
        return got

    def demote_key(self, type_name: str, key: int) -> int:
        """Fold a replicated grain back to one row (the controller's
        cool-down path).  Same drain-first discipline as promotion.
        Returns secondary rows freed."""
        arena = self.arenas.get(type_name)
        if arena is None:
            return 0
        if self._pending_checks or self._exchange_checks \
                or self._fanout_checks:
            self._drain_checks()
        freed = arena.demote_replicas(key)
        if freed:
            self.replica_demotions += 1
        return freed

    async def reshard(self, mesh: Optional[jax.sharding.Mesh]) -> None:
        """Re-lay every arena over a new mesh — the data-plane elasticity
        event (a device/"silo" joining or leaving).  Quiesces in-flight
        work first so the move is tick-consistent, then rebuilds each
        arena's blocks by the stable key hash (reference analog: directory
        handoff on membership change,
        GrainDirectoryHandoffManager.cs:141)."""
        await self.flush()
        # attribution counts fold to the host retired mirror FIRST,
        # while every arena's key→row map still describes the rows the
        # counts were accumulated against (arena.reshard hooks the same
        # fold for direct calls; fold_type is idempotent)
        self.attribution.relocate()
        self._apply_mesh(mesh)
        for arena in self.arenas.values():
            arena.reshard(self.n_shards, self.sharding)
        # sharded array shapes changed: compiled steps specialize on shard
        # layout, so drop them and let jit re-trace on next use
        self._step_cache.clear()
        # churn attribution: recompiles of signatures the reshard forgot
        # are caused by the reshard, not by new traffic shapes (keyed
        # WITHOUT capacity — the reshard itself changes it)
        self.reshard_count += 1
        self._reshard_forgotten = {(s[0], s[1], s[2])
                                   for s in self._seen_steps}
        self._seen_steps = set()
        # the ledger hist may be committed to the OLD device set (fused
        # windows return it as a program output) — fold counts to host
        # and let the next record recreate it on the new set
        self.ledger.relocate()

    async def checkpoint(self) -> int:
        """Tick-consistent snapshot: quiesce, then write every live row of
        every arena through the store.  Returns rows written."""
        await self.flush()
        return sum(a.checkpoint() for a in self.arenas.values())

    def maybe_periodic_checkpoint(self) -> float:
        """Bounded-loss-window write-back (config checkpoint_every_ticks):
        fires whenever the tick clock has advanced past the cadence since
        the last write — called at unfused tick boundaries AND after fused
        windows (which advance tick_number by whole windows), so the
        promised bound holds in the fused steady state too.  At a tick or
        window boundary the state is consistent, so this is a valid
        restore point for survivors after a hard kill.  Returns seconds
        spent (0.0 when it did not fire)."""
        if not self.checkpoint_due():
            return 0.0
        if self._exchange_checks and self._drain_exchange_checks():
            # exchange-overflow redeliveries just requeued: their SOURCE
            # updates have not applied yet, but their fan-out subscriber
            # deliveries (expanded in the original pass) may have — a
            # checkpoint now could persist subscriber effects without
            # the source update.  Defer the write one tick (the drain's
            # batched stat read decides: the common no-drop steady state
            # proceeds, so continuous traffic cannot starve the
            # cadence); checkpoint_due() keeps firing until it lands.
            return 0.0
        t_cp = time.perf_counter()
        for a in self.arenas.values():
            if a.store is not None:
                a.checkpoint()
        self._last_checkpoint_tick = self.tick_number
        return time.perf_counter() - t_cp

    def checkpoint_due(self) -> bool:
        """True when the periodic checkpoint cadence has elapsed — the
        predicate of maybe_periodic_checkpoint, shared so the auto-fuser
        can settle its verification chain before a due write."""
        cadence = self.config.checkpoint_every_ticks
        return cadence > 0 and \
            self.tick_number - self._last_checkpoint_tick >= cadence

    def restore(self, type_names: Optional[List[str]] = None) -> int:
        """Re-activate all stored rows (process-restart resume).  With no
        argument every registered @vector_grain type is tried — arenas are
        created lazily, so the engine's own arena dict is empty right after
        a restart and must not be the default."""
        from orleans_tpu.tensor.vector_grain import all_vector_types
        names = type_names if type_names is not None \
            else list(all_vector_types())
        return sum(self.arena_for(n).restore_from_store() for n in names)

    # ================= submission (the client/batch edge) =================

    @staticmethod
    def _type_name(interface) -> str:
        return interface if isinstance(interface, str) else interface.__name__

    def send_batch(self, interface, method: str, keys: np.ndarray, args: Any,
                   want_results: bool = False) -> Optional[asyncio.Future]:
        """Bulk message injection — the TPU-native client edge: one call
        carries a whole (dst, payload) tensor (north star: 'batched
        adjacency+payload tensors').

        In cluster mode host-key batches route through the VectorRouter:
        the local partition enqueues here, remote partitions ship as slabs
        to their ring owners.  Device-key batches stay local — remote keys
        surface as optimistic-resolution misses and ship at the next
        quiescence point (see _drain_checks)."""
        type_name = self._type_name(interface)
        if self.router is not None:
            if (isinstance(keys, jnp.ndarray) and keys.dtype == jnp.int32
                    and not want_results):
                # pure optimistic device path: remote keys surface as
                # misses and ship at the quiescence point
                return self.enqueue_local_batch(type_name, method, keys,
                                                args)
            # everything else resolves eagerly on the host, which would
            # activate remote-owned keys locally — route instead
            return self.router.route_batch(type_name, method,
                                           np.asarray(keys), args,
                                           want_results=want_results)
        return self.enqueue_local_batch(type_name, method, keys, args,
                                        want_results=want_results)

    def enqueue_local_batch(self, type_name: str, method: str,
                            keys, args: Any, want_results: bool = False
                            ) -> Optional[asyncio.Future]:
        """Queue a batch on THIS engine without ownership routing (the
        router calls this for partitions it has already proven local)."""
        future = asyncio.get_running_loop().create_future() \
            if want_results else None
        # tracing: carry the enqueuer's ambient trace so the executing
        # tick's batched span can link back to the request (spans.py).
        # Only SAMPLED traces are worth carrying — link events exist
        # only for them, so unsampled ones would ride for nothing.
        trace = None
        if self._span_recorder() is not None:
            from orleans_tpu.spans import current_trace
            t = current_trace()
            if t is not None and t.get("sampled"):
                trace = t
        if (isinstance(keys, jnp.ndarray) and keys.dtype == jnp.int32
                and not want_results):
            # device keys resolve optimistically (unseen keys re-delivered
            # later) — that cannot retroactively fix an already-resolved
            # result future, so want_results forces the host path
            batch = PendingBatch(args=args, keys_dev=keys, future=future,
                                 trace=trace, inject_tick=self.tick_number)
        else:
            batch = PendingBatch(args=args,
                                 keys_host=np.asarray(keys, dtype=np.int64),
                                 future=future, trace=trace,
                                 inject_tick=self.tick_number)
        if (type_name, method) in self._journal_sites:
            # durable state plane: journal the ingress BEFORE it can
            # execute (write-ahead — the device ring append is one
            # dispatch; durability lands at segment seal)
            self.checkpointer.journal_ingress(type_name, method, batch)
        self.queues[(type_name, method)].append(batch)
        self._wake_up()
        return future

    def register_journal(self, interface, method: str,
                         emit_key_args: Tuple[str, ...] = ()) -> None:
        """Mark (interface, method) as a JOURNALED ingress site: every
        batch entering through send_batch/enqueue/injectors appends to
        the device journal ring, seals into durable segments, and
        fold-replays after a crash (tensor/checkpoint.py).  The device
        tier of event_sourcing.py's JournaledGrain — per-tick batched
        appends instead of per-event storage commits.
        ``emit_key_args`` names arg leaves holding emit-destination
        keys of this same grain type (e.g. a transfer's ``dst``) so
        fused fold-replay can pre-activate them (see
        CheckpointPlane.register_journal)."""
        self.checkpointer.register_journal(
            interface, method, emit_key_args=emit_key_args)

    def register_fanout(self, src_interface, src_method: str, fanout,
                        dst_interface, dst_method: str) -> None:
        """Every message delivered to (src_interface, src_method) also
        expands through ``fanout`` (a DeviceFanout subscription graph) into
        messages for (dst_interface, dst_method) — the batched analog of a
        grain forwarding each message to its subscriber set (reference:
        ChirperAccount.PublishMessage → Followers loop,
        ChirperAccount.cs:129-156; ObserverSubscriptionManager.Notify).
        Expansion runs on device and the expanded batch routes through the
        normal emit path next round (same tick)."""
        self._fanouts[(self._type_name(src_interface), src_method)] = (
            fanout, self._type_name(dst_interface), dst_method)

    def register_subscriptions(self, src_interface, src_method: str,
                               subscriptions) -> None:
        """The streams plane's engine edge (tensor/streams_plane.py):
        every message delivered to (src_interface, src_method) — the
        stream-ingress method, rows = streams — also fans out to the
        stream's subscribers through ``subscriptions`` into its bound
        delivery edge.  Publishes matching the bound key set take the
        pull path (one payload gather + one segment_sum, scatter-free);
        everything else expands push-mode to subscriber keys with
        overflow parking."""
        self._stream_routes[(self._type_name(src_interface), src_method)] \
            = subscriptions

    def _route_expand_push(self, expander, dst_type: str, dst_method: str,
                           skeys, args: Any, mask, inject_tick: int,
                           keys_host: Optional[np.ndarray] = None
                           ) -> None:
        """Shared push-expansion tail for DeviceFanout registrations and
        stream-subscription routes: expand, enqueue the subscriber
        deliveries, and PARK the expansion's device-side overflow mask
        — dropped source lanes re-expand at the next quiescence point
        with their original stamp (never a mid-tick error).  A
        DeviceFanout given the round's ``keys_host`` sizes the
        expansion to their degree sum."""
        sized = {} if keys_host is None else {"keys_host": keys_host}
        dst, gargs, valid = expander.expand(skeys, args, mask, **sized)
        count, dropped = expander.take_drop()
        self._fanout_checks.append(_FanoutCheck(
            expander=expander, dst_type=dst_type, dst_method=dst_method,
            keys=skeys, args=args, dropped=dropped, count=count,
            inject_tick=inject_tick))
        self.queues[(dst_type, dst_method)].append(
            PendingBatch(args=gargs, keys_dev=dst, mask=valid,
                         inject_tick=self.tick_number))
        if hasattr(expander, "push_deliveries"):
            expander.push_deliveries += 1

    def _run_fanout(self, type_name: str, method: str,
                    batches: List[PendingBatch]) -> None:
        fan = self._fanouts.get((type_name, method))
        if fan is None:
            return
        fanout, dst_type, dst_method = fan
        for b in batches:
            if b.no_fanout:
                continue
            mask = b.mask
            if b.keys_dev is not None:
                # device-key sources expand AFTER resolution, inside
                # _run_group (_expand_resolved_fanout): the SAME resolve
                # that applies the batch gates its expansion, so a source
                # entry that misses (unseen grain) does not fan out until
                # its miss-path redelivery applies — source update and
                # subscriber delivery land in the same tick, which a
                # tick-boundary checkpoint relies on.  Host-key batches
                # resolve inline (activation precedes apply), so they
                # expand here as before.
                continue
            if b.keys_host is not None:
                if (b.keys_host >= KEY_SENTINEL).any() or \
                        (b.keys_host < 0).any():
                    raise OverflowError(
                        "fanout src keys must be in [0, 2**31-1)")
                skeys = jnp.asarray(b.keys_host.astype(np.int32))
            elif b.keys_wide is not None:
                # same contract as the host-key case, surfaced loudly
                # instead of silently dropping the subscriber deliveries
                raise OverflowError(
                    "fanout expansion requires narrow int keys in "
                    "[0, 2**31-1); wide (hi, lo) source keys cannot map "
                    "through the CSR subscription graph")
            else:
                continue  # row-only batch with no kept keys: nothing to map
            self._route_expand_push(fanout, dst_type, dst_method,
                                    skeys, b.args, mask, b.inject_tick,
                                    keys_host=b.keys_host)

    def _expand_resolved_fanout(self, fan, batches: List[PendingBatch],
                                resolved: List[Tuple]) -> None:
        """Device-key fan-out expansion, gated by the SAME resolution the
        apply step uses (one resolve dispatch; the gate and the miss
        check cannot disagree): hit entries expand now — their subscriber
        deliveries run next round of this tick, exactly where
        _run_fanout's pre-group expansion would have put them — and
        missed entries expand when their miss-path redelivery applies."""
        fanout, dst_type, dst_method = fan
        for b, (rows, _args) in zip(batches, resolved):
            if b.no_fanout or b.keys_dev is None:
                continue
            base = b.mask if b.mask is not None \
                else _mask_for(b.keys_dev.shape[0])
            self._route_expand_push(fanout, dst_type, dst_method,
                                    b.keys_dev, b.args,
                                    base & (rows >= 0), b.inject_tick)

    # -- stream-subscription routes (tensor/streams_plane.py) ---------------

    def _to_host_batch(self, b: PendingBatch) -> PendingBatch:
        """Convert a device-key batch to a host-key batch (the streams
        plane's live-disabled baseline pays the d2h; masked lanes are
        filtered on host — host-key batches carry no mask)."""
        if b.keys_host is not None and b.mask is None:
            return b
        keys = b.keys_host if b.keys_host is not None \
            else np.asarray(b.keys_dev).astype(np.int64)
        args = jax.tree_util.tree_map(np.asarray, b.args)
        if b.mask is not None:
            sel = np.asarray(b.mask)
            keys = keys[sel]
            args = jax.tree_util.tree_map(
                lambda a: a if np.ndim(a) == 0 else a[sel], args)
        return PendingBatch(args=args, keys_host=keys,
                            no_fanout=b.no_fanout, trace=b.trace,
                            inject_tick=b.inject_tick)

    def _run_stream_routes_pre(self, type_name: str, method: str,
                               batches: List[PendingBatch]
                               ) -> List[PendingBatch]:
        """Pre-resolve half of the stream-route expansion, mirroring
        _run_fanout: host-key publishes expand here (activation precedes
        apply on the host path), device-key publishes expand after
        resolution.  With the plane live-disabled this is the HOST
        baseline: publishes convert to host batches and the adjacency
        walks in numpy — the per-event-era delivery path the streams
        bench A/Bs the device plane against."""
        route = self._stream_routes.get((type_name, method))
        if route is None:
            return batches

        def expand_on_host(b2: PendingBatch) -> None:
            route.published_events += len(b2)
            dst_keys, src_idx = route.host_expand(b2.keys_host)
            if len(dst_keys) == 0:
                return
            gargs = jax.tree_util.tree_map(
                lambda a: a if np.ndim(a) == 0
                else np.asarray(a)[src_idx], b2.args)
            if isinstance(gargs, dict) and "src_key" not in gargs:
                gargs = {**gargs,
                         "src_key": (b2.keys_host[src_idx]
                                     % np.int64(KEY_SENTINEL))
                         .astype(np.int32)}
            self.queues[(route.type_name, route.method)].append(
                PendingBatch(args=gargs,
                             keys_host=dst_keys.astype(np.int64),
                             inject_tick=self.tick_number))
            route.delivered_events += len(dst_keys)

        if not self._streams_live():
            out: List[PendingBatch] = []
            for b in batches:
                if b.no_fanout or (b.keys_host is None
                                   and b.keys_dev is None):
                    out.append(b)
                    continue
                b2 = self._to_host_batch(b)
                out.append(b2)
                expand_on_host(b2)
            return out
        for b in batches:
            if b.no_fanout or b.keys_dev is not None \
                    or b.keys_host is None:
                continue  # device-key publishes expand post-resolve
            if (b.keys_host >= KEY_SENTINEL).any() \
                    or (b.keys_host < 0).any():
                # wide stream identities: the device CSR is int31-keyed
                # — deliver through the host expansion instead of
                # erroring mid-tick (the round's other popped groups
                # must never be lost to one wide key)
                expand_on_host(b)
                continue
            route.published_events += len(b)
            self._route_expand_push(
                route, route.type_name, route.method,
                jnp.asarray(b.keys_host.astype(np.int32)), b.args,
                b.mask, b.inject_tick)
        return batches

    def _expand_resolved_stream_routes(self, route, type_name: str,
                                       method: str,
                                       batches: List[PendingBatch],
                                       resolved: List[Tuple]) -> None:
        """Device-key publish expansion, resolution-gated like
        _expand_resolved_fanout.  A publish batch matching the route's
        BOUND key set takes the pull path: the subscriber deliveries
        enqueue as ONE row-addressed, segment-offset batch (payload
        gathered per edge — zero resolution, zero scatters downstream);
        anything else expands push-mode to subscriber keys."""
        dst_arena = self.arena_for(route.type_name)
        for b, (rows, _args) in zip(batches, resolved):
            if b.no_fanout or b.keys_dev is None \
                    or b.segments is not None:
                continue
            base = b.mask if b.mask is not None \
                else _mask_for(b.keys_dev.shape[0])
            gate = base & (rows >= 0)
            route.published_events += len(b)
            pull = route.pull_layout(dst_arena) \
                if route._matches_bound(b.keys_host) else None
            if pull is not None and pull["n_edges"] > 0:
                lane = pull["src_lane"]
                gargs = jax.tree_util.tree_map(
                    lambda a: a if jnp.ndim(a) == 0
                    else jnp.asarray(a)[lane], b.args)
                if isinstance(gargs, dict) and "src_key" not in gargs:
                    gargs = {**gargs, "src_key": pull["src_key"]}
                self.queues[(route.type_name, route.method)].append(
                    PendingBatch(
                        args=gargs, rows=pull["rows"],
                        keys_dev=pull["dst_key"], mask=gate[lane],
                        segments=pull["offsets"],
                        generation=dst_arena.generation,
                        epoch=dst_arena.eviction_epoch,
                        inject_tick=self.tick_number))
                route.pull_deliveries += 1
                route.delivered_events += pull["n_edges"]
            else:
                self._route_expand_push(
                    route, route.type_name, route.method,
                    b.keys_dev, b.args, gate, b.inject_tick)

    def make_injector(self, interface, method: str, keys: np.ndarray):
        """Pre-resolve a stable destination set once; subsequent injections
        are zero-lookup (the gateway's steady-state client edge).  In
        cluster mode the split by ring owner is part of what's resolved
        once (router.make_injector → ClusterInjector)."""
        type_name = self._type_name(interface)
        keys = np.asarray(keys, dtype=np.int64)
        if self.router is not None:
            return self.router.make_injector(type_name, method, keys)
        return BatchInjector(self, type_name, method, keys)

    def fuse_ticks(self, interface, method: str, keys: np.ndarray):
        """Compile the steady-state tick for (interface, method) over a
        fixed key set into one multi-tick device program (tensor/fused.py
        — one dispatch per WINDOW instead of several per tick).  The
        returned FusedTickProgram's ``run(stacked_args)`` executes a
        whole [T, ...] window; ``verify()`` must report 0 misses for the
        window to be exact.

        Fused windows are single-engine programs: on a clustered silo the
        key set must be entirely ring-owned here (fuse each silo's own
        partition; cross-silo traffic rides the slab path instead)."""
        type_name = self._type_name(interface)
        keys = np.asarray(keys, dtype=np.int64)
        if self.router is not None:
            local_mask, remote = self.router.partition(type_name, keys)
            if remote:
                raise ValueError(
                    f"fuse_ticks({type_name}): {int((~local_mask).sum())} "
                    f"of {len(keys)} keys are ring-owned by other silos; "
                    "a fused window would activate them locally (duplicate "
                    "activation). Fuse only keys[local] per silo — "
                    "partition with silo.vector_router.partition().")
        from orleans_tpu.tensor.fused import FusedTickProgram
        return FusedTickProgram(self, type_name, method, keys)

    def send_one(self, grain_id: GrainId, method: MethodInfo,
                 args: tuple) -> Optional[asyncio.Future]:
        """Single-message path used by GrainReference proxies — vector
        grains stay callable exactly like host grains."""
        info = vector_type(grain_id.type_code)
        if info is None:
            raise KeyError(f"{grain_id} is not a vector grain")
        payload = args[0] if args else {}
        one = jax.tree_util.tree_map(lambda x: np.asarray([x]), payload)
        fut = self.send_batch(info.name, method.name,
                              np.array([grain_id.primary_key_int]), one,
                              want_results=not method.one_way)
        if fut is None:
            return None
        loop = asyncio.get_running_loop()
        scalar: asyncio.Future = loop.create_future()

        def unwrap(f: asyncio.Future) -> None:
            if scalar.done():
                return
            if f.exception() is not None:
                scalar.set_exception(f.exception())
            else:
                res = f.result()
                scalar.set_result(
                    jax.tree_util.tree_map(lambda x: np.asarray(x)[0], res)
                    if res is not None else None)

        fut.add_done_callback(unwrap)
        return scalar

    # ================= tick loop ==========================================

    def start(self) -> None:
        self._running = True
        self._wake = asyncio.Event()
        self._task = asyncio.get_running_loop().create_task(self._loop())

    async def stop(self, drain: bool = True) -> None:
        if drain and self._running:
            await self.flush()
        self._running = False
        if self._wake is not None:
            self._wake.set()
        if self._task is not None:
            await self._task
            self._task = None
        # settle in-flight completion futures so no executor thread
        # outlives the engine holding fence references
        await self.pipeline.drain()
        # never leave a triggered jax.profiler capture session dangling
        self.profiler.shutdown()

    def _wake_up(self) -> None:
        if self._wake is not None:
            self._wake.set()

    def check_health(self) -> bool:
        """Watchdog participant: the tick loop must be alive while the
        engine runs (a dead loop silently strands every queued batch)."""
        if not self._running:
            return True
        return self._task is not None and not self._task.done()

    async def _loop(self) -> None:
        while self._running:
            await self._wake.wait()
            self._wake.clear()
            while self._running:
                while self._running and any(self.queues.values()):
                    self.run_tick()
                    # pipelined pacing: register the tick's completion
                    # event and, with pipeline_depth ticks in flight,
                    # await the OLDEST completion (event-driven
                    # backpressure — the device sets the pace, no poll)
                    self.pipeline.note_tick(self._tick_fence)
                    await self.pipeline.throttle()
                    # yield so producers can batch up the next tick; the
                    # accumulation interval is the latency/throughput knob
                    await asyncio.sleep(self.tick_interval())
                if self._drain_checks():
                    continue
                if self._running and self.autofuser.has_buffer():
                    # partially-filled fused window and no new work: give
                    # the producer one grace period to continue the
                    # pattern, then replay the buffer unfused so buffered
                    # ticks never strand awaiting an explicit flush()
                    try:
                        await asyncio.wait_for(
                            self._wake.wait(),
                            timeout=self.config.auto_fusion_idle_flush)
                        self._wake.clear()
                        continue
                    except asyncio.TimeoutError:
                        self.autofuser.idle_flush()
                        continue
                break

    async def drain_queues(self) -> None:
        """Dispatch all queued work to the device without waiting for
        deferred miss-checks (the pipelined steady-state path)."""
        while any(self.queues.values()):
            self.run_tick()
            if self.router is not None \
                    and not self.router.handoff_settled():
                # the handoff fence is re-queueing unseen-key batches —
                # pace the retries instead of busy-spinning at sleep(0)
                # for the whole fence window
                await asyncio.sleep(0.002)
            else:
                await asyncio.sleep(0)

    async def flush(self) -> None:
        """Run ticks until every queue drains AND all optimistic
        miss-checks have settled (full delivery — tests/benchmark ends).
        Partially-filled auto-fusion windows replay unfused here, one
        buffered tick at a time (exact per-tick order)."""
        while True:
            await self.drain_queues()
            requeued = self._drain_checks()
            if self.autofuser.flush_partial():
                requeued = True
            if not requeued:
                if self.router is not None \
                        and getattr(self.router, "_retry_tasks", None):
                    # parked cross-silo redelivery (bounced / over-
                    # forwarded slabs awaiting backoff) is in-flight
                    # work — full delivery waits it out; the retry
                    # budget bounds this (drops are logged + counted)
                    await asyncio.sleep(0.01)
                    continue
                break
            if self.router is not None \
                    and not self.router.handoff_settled():
                # the handoff fence is deferring unseen-key activation —
                # pace the retry loop while awaiting peers' releases
                await asyncio.sleep(0.005)
        # quiescence point: fold any un-taken expansion drop masks into
        # the host stats (engine-driven expansions take theirs eagerly;
        # this covers direct expand() users).  Parked overflow lanes
        # were all redelivered by the drain loop above — overflow is a
        # redelivery event now, never an error (satellite contract).
        for fanout, _, _ in self._fanouts.values():
            fanout.overflow_check()
        for route in self._stream_routes.values():
            route.overflow_check()

    # ================= event-driven completion ============================

    def completion_future(self):
        """An awaitable resolving when every device program dispatched so
        far has completed — the event-driven replacement for host-side
        ``block_until_ready`` on arena columns.  Blocks on the latest
        tick's FENCE output (which nothing ever donates, so the wait is
        safe even while later ticks donate the state buffers away);
        programs execute in dispatch order per device, so the latest
        fence's readiness implies everything before it.  None when no
        tick has dispatched yet."""
        if self._tick_fence is None:
            return None
        loop = asyncio.get_running_loop()
        return loop.run_in_executor(None, _fence_block, self._tick_fence)

    async def wait_completion(self) -> None:
        """Await full device completion of all dispatched work: drain the
        pipeline's in-flight ticks, then the latest fence.  The one sync
        point benches/tests need — a message's observed completion is the
        device event, not the next poll."""
        await self.pipeline.drain()
        fut = self.completion_future()
        if fut is not None:
            await fut

    # ================= tick execution =====================================

    def run_tick(self) -> None:
        if self.autofuser.offer():
            # the tick was consumed into (or ran as part of) a fused
            # window — counters/latency are accounted by the window run
            return
        prof = self.profiler
        rec = self._span_recorder()
        if rec is not None:
            self._tick_traces = []
            self._tick_counts = defaultdict(int)
            span_msgs0 = self.messages_processed
            span_compiles0 = self.compile_count()
            span_start = time.monotonic()
        with prof.tick() as tick:
            rounds, timers_fired = self._tick_body()
        dt = tick.seconds
        stages = prof.tick_stages
        self.tick_seconds += dt
        self.tick_durations.append(dt)
        # tick-phase profiler (tensor/profiler.py): fold the stage
        # timers into the five canonical phases + trigger deep capture
        # on a wall-time breach; compile events recorded this tick ride
        # the batched span so a slow tick names its compile.  Pipelined
        # ticks overlap device work with later host work — observe_tick
        # pulls the accrued credit from the pipeline for reconciliation.
        if self.profiler.enabled:
            phases = self.profiler.observe_tick(dt, stages)
        else:
            phases = None
            # discard the credit while profiling is off: left to accrue,
            # the whole backlog would land on the first observed tick
            # after a live re-enable and blind its overrun detector
            self.pipeline.take_tick_overlap()
        compile_events = self.compile_tracker.drain_tick_events()
        if rec is not None and stages.get("fanout"):
            # stream-plane episode: this tick's subscription fan-out /
            # routing work, one interval on the streams track
            rec.plane_span("streams", "fan-out tick",
                           duration=stages["fanout"],
                           tick=self.tick_number, rounds=rounds)
        if rec is not None and timers_fired:
            rec.plane_span("timers", "advance",
                           duration=stages["timers"],
                           tick=self.tick_number,
                           armed=self.timers.armed_total)
        if rec is not None:
            # ONE batched span per tick (batch size, per-type counts,
            # compile events) + link events into the sampled traces it
            # executed — never per-message device spans (stats.py note)
            rec.tick_span(
                tick=self.tick_number, start=span_start, duration=dt,
                messages=self.messages_processed - span_msgs0,
                rounds=rounds, per_method=dict(self._tick_counts),
                compiles=self.compile_count() - span_compiles0,
                traces=self._tick_traces, phases=phases,
                compile_events=compile_events)
            self._tick_traces = []
        # unconditionally: an active capture must count down (and stop)
        # even if the profiler was live-disabled mid-capture
        self.profiler.tick_done()
        self._adapt(dt)

    def _tick_body(self) -> Tuple[int, float]:
        """One tick's work inside its ``orleans.tick`` stage; returns
        the rounds run and the seconds the timer plane reported."""
        prof = self.profiler
        self.tick_number += 1
        self.ticks_run += 1
        cfg = self.config
        if cfg.collection_idle_ticks and cfg.collection_every_ticks > 0:
            # incremental collection: the cadence tick SELECTS victims
            # (device compare, mask-only transfer); every tick thereafter
            # drains one pause-budgeted slice until the sweep finishes.
            # The tick never stalls past the budget + one chunk.
            with prof.stage("collect"):
                if (not self.collector.active()
                        and self.tick_number
                        % cfg.collection_every_ticks == 0):
                    self.collector.start_sweep(
                        self.tick_number - cfg.collection_idle_ticks)
                if self.collector.active():
                    self.collector.run_slice(cfg.collection_pause_budget_s,
                                             cfg.collection_chunk_rows)
        timers_fired = 0.0
        if cfg.timers_plane and self.timers.armed_total:
            # harvest due timers BEFORE the rounds loop so fired
            # batches deliver within this same tick
            with prof.stage("timers"):
                timers_fired = self.timers.advance_to(self.tick_number)
        if len(self._pending_checks) + len(self._exchange_checks) \
                + len(self._fanout_checks) >= self.config.miss_check_cap:
            # bound device memory pinned by parked optimistic checks
            # (exchange and fan-out overflow checks pin their batch's
            # args the same way, so they count against the same cap)
            self._drain_checks()
        rounds = 0
        while rounds < self.config.max_rounds_per_tick:
            pending = {k: v for k, v in self.queues.items() if v}
            if not pending:
                break
            self.queues = defaultdict(list)
            if self._exchange_live() and self.config.exchange_overlap \
                    and self.router is None and self.exchange.engaged():
                self._pre_exchange_round(pending)
            for (type_name, method), batches in pending.items():
                with prof.stage("fanout"):
                    if self.router is not None:
                        # ownership + handoff fence BEFORE fan-out:
                        # shipped and fence-deferred batches must not
                        # expand their subscriber deliveries locally
                        batches = self._route_group(type_name, method,
                                                    batches)
                    local = bool(batches)
                    if local:
                        self._run_fanout(type_name, method, batches)
                        batches = self._run_stream_routes_pre(
                            type_name, method, batches)
                if local:
                    self._run_group(type_name, method, batches)
            rounds += 1
            self.rounds_run += 1
        if self._fence_deferred:
            for qkey, b in self._fence_deferred:
                self.queues[qkey].append(b)
            self._fence_deferred = []
        with prof.stage("checkpoint"):
            self.maybe_periodic_checkpoint()
            # durable state plane: start/advance a due snapshot drain
            # under its pause budget + keep the journal segment cadence
            self.checkpointer.on_tick()
        return rounds, timers_fired

    def tick_interval(self) -> float:
        """Seconds to accumulate messages before the next tick."""
        if self.config.low_latency:
            # the honest 10ms mode: the pipeline's completion events set
            # the pace; the sleep only yields to producers
            return self.config.tick_interval_min
        if self.config.target_tick_latency <= 0:
            return self.config.tick_interval
        return self._adaptive_interval

    def _adapt(self, tick_duration: float) -> None:
        """Adaptive tick sizing: a message's turn latency is bounded by
        accumulation wait + tick service time, so steer the accumulation
        interval to keep that sum inside ``target_tick_latency``.  Longer
        intervals build bigger batches (throughput); the controller grows
        the interval only while the budget has headroom and cuts it
        multiplicatively when a tick overruns.  The controller judges the
        raw measured duration: completion is observed event-driven now,
        so there is no rig observation floor left to net out."""
        budget = self.config.target_tick_latency
        if budget <= 0:
            return
        cfg = self.config
        if tick_duration + self._adaptive_interval > budget:
            self._adaptive_interval = max(cfg.tick_interval_min,
                                          self._adaptive_interval * 0.5)
        else:
            headroom = budget - tick_duration
            self._adaptive_interval = max(
                cfg.tick_interval_min,
                min(cfg.tick_interval_max, headroom * 0.5,
                    self._adaptive_interval * 1.1 + 1e-5))

    def latency_stats(self) -> Dict[str, float]:
        """True percentiles over the recent per-tick duration window (NOT
        a mean — the north-star metric's p99 is a real p99 here)."""
        if not self.tick_durations:
            return {"n": 0, "p50": 0.0, "p90": 0.0, "p99": 0.0,
                    "mean": 0.0, "max": 0.0}
        d = np.asarray(self.tick_durations)
        return {
            "n": int(d.size),
            "p50": float(np.percentile(d, 50)),
            "p90": float(np.percentile(d, 90)),
            "p99": float(np.percentile(d, 99)),
            "mean": float(d.mean()),
            "max": float(d.max()),
        }

    # -- destination resolution --------------------------------------------

    def _resolve_batch(self, arena: GrainArena, b: PendingBatch,
                       method: str) -> Tuple[jnp.ndarray, Any]:
        """Normalize a batch to (rows int32[m] device, args device).

        Device-key batches resolve *optimistically*: messages to unseen
        keys get row -1 (dropped by the kernels) and a deferred miss-check
        is parked; at the next quiescence point the engine activates the
        unseen keys and re-delivers exactly the dropped messages.  This is
        the batched analog of at-least-once delivery with resend
        (reference: CallbackData resend, Dispatcher rerouting) and keeps
        the hot path free of host synchronization."""
        args = b.args
        if b.rows is not None and b.generation == arena.generation \
                and b.epoch == arena.eviction_epoch:
            return b.rows, args
        if b.keys_host is not None:
            # pre-resolved rows gone stale fall through to here too,
            # re-resolving from the kept keys: a generation mismatch
            # means growth repacked rows; an epoch mismatch means rows
            # were freed since resolution — re-resolution re-activates
            # any evicted key (through the store) before applying
            rows = arena.resolve_rows(b.keys_host, tick=self.tick_number)
            if arena._replicas:
                rows = arena.spread_rows_host(rows)
            return rows.astype(np.int32), args  # numpy → host-pad path
        keys = b.keys_wide if b.keys_wide is not None else b.keys_dev
        m = keys[0].shape[0] if isinstance(keys, tuple) else keys.shape[0]
        valid = b.mask if b.mask is not None \
            else jnp.ones(m, dtype=bool)
        rows, miss_count = resolve_rows_on_device(arena, keys, valid)
        self._pending_checks.append(
            _MissCheck(arena=arena, type_name=arena.info.name,
                       method=method, keys=keys, valid=valid,
                       rows=rows, miss_count=miss_count, args=args,
                       inject_tick=b.inject_tick))
        return rows, args

    def _drain_checks(self) -> bool:
        """Quiescence point: activate unseen keys discovered by optimistic
        resolution and re-deliver their (and only their) messages.
        Returns True if new work was queued."""
        if not self._pending_checks and not self._exchange_checks \
                and not self._fanout_checks:
            return False
        # within a tick the drain is part of that tick's breakdown;
        # between ticks it accrues to the cumulative totals directly
        with self.profiler.stage("miss_checks"):
            return self._drain_parked_checks()

    def _drain_parked_checks(self) -> bool:
        checks = self._pending_checks
        self._pending_checks = []
        requeued = self._drain_exchange_checks()
        if self._drain_fanout_checks():
            requeued = True
        # one batched sync for all parked counts — a single device
        # transfer regardless of how many checks are parked.  The arity
        # pads to the next power of two so the varargs jit compiles
        # O(log cap) programs, not one per distinct count
        if len(checks) == 1:
            counts = [int(host_read("miss_checks", checks[0].miss_count))]
        else:
            n = len(checks)
            padded = 1 << (n - 1).bit_length()
            xs = [c.miss_count for c in checks] \
                + [np.int32(0)] * (padded - n)
            counts = np.asarray(host_read(
                "miss_checks", _stack_counts(*xs)))[:n].tolist()
        for c, cnt in zip(checks, counts):
            if cnt == 0:
                continue
            self.activation_passes += 1
            if isinstance(c.keys, tuple):
                # wide keys: redeliver the missed entries through the
                # exact HOST path (reconstructed int64 keys) — activates,
                # routes ownership, and cannot loop on pathological
                # bucket-collision runs the device probes cannot resolve
                from orleans_tpu.tensor.arena import join_wide_keys
                missing_np = np.asarray((np.asarray(c.rows) < 0)
                                        & np.asarray(c.valid))
                idx = np.nonzero(missing_np)[0]
                if len(idx) == 0:
                    continue
                keys64 = join_wide_keys(np.asarray(c.keys[0])[idx],
                                        np.asarray(c.keys[1])[idx])
                args_h = jax.tree_util.tree_map(np.asarray, c.args)
                self.queues[(c.type_name, c.method)].append(PendingBatch(
                    args=jax.tree_util.tree_map(
                        lambda a: a if np.ndim(a) == 0 else a[idx],
                        args_h),
                    keys_host=keys64, no_fanout=True,
                    inject_tick=c.inject_tick))
                requeued = True
                continue
            miss_keys, missing = _miss_keys_kernel(c.keys, c.rows, c.valid,
                                                   miss_buf=MISS_BUF)
            mk = np.asarray(host_read("miss_checks", miss_keys))
            mk = mk[mk != KEY_SENTINEL].astype(np.int64)
            if self.router is not None and len(mk):
                # single-activation across silos: a miss key owned by a
                # remote silo must NOT activate here — its messages are
                # extracted and shipped to the owner as one slab per
                # destination (tensor/router.py)
                local_mask, remote = self.router.partition(c.type_name, mk)
                if remote:
                    keys_np = np.asarray(c.keys)
                    missing_np = np.array(missing)  # writable host copy
                    args_h = jax.tree_util.tree_map(np.asarray, c.args)
                    shipped = np.zeros(len(keys_np), dtype=bool)
                    for target, ridx in remote.items():
                        sel = missing_np & np.isin(
                            keys_np, mk[ridx].astype(keys_np.dtype))
                        if not sel.any():
                            continue
                        sidx = np.nonzero(sel)[0]
                        self.router.ship_slab(
                            target, c.type_name, c.method,
                            keys_np[sidx].astype(np.int64),
                            jax.tree_util.tree_map(
                                lambda a: a if np.ndim(a) == 0
                                else a[sidx], args_h))
                        shipped |= sel
                    mk = mk[local_mask]
                    missing_np &= ~shipped
                    if len(mk) == 0 and not missing_np.any():
                        continue  # whole batch shipped — nothing local left
                    missing = jnp.asarray(missing_np)
            if len(mk) and self.router is not None \
                    and not self.router.handoff_settled():
                # handoff fence: activating these unseen keys could read
                # the store before the previous owner's write-back lands —
                # requeue and retry once peers release (or timeout).
                # no_fanout while fenced: every masked entry is known
                # unresolvable, so expansion would only enqueue phantom
                # all-masked destination batches each retry cycle; the
                # post-settle requeue below re-enables fan-out.
                self.queues[(c.type_name, c.method)].append(PendingBatch(
                    args=c.args, keys_dev=c.keys, mask=missing,
                    no_fanout=True, inject_tick=c.inject_tick))
                requeued = True
                continue
            if len(mk):
                c.arena.resolve_rows(mk, tick=self.tick_number)  # activates
            # re-deliver only the dropped messages (fan-out enabled — see
            # the fenced requeue above); convergence across cycles even
            # when unique misses exceed MISS_BUF
            self.queues[(c.type_name, c.method)].append(PendingBatch(
                args=c.args, keys_dev=c.keys, mask=missing,
                inject_tick=c.inject_tick))
            requeued = True
        return requeued

    def _drain_fanout_checks(self) -> bool:
        """Quiescence half of the fan-out/subscription overflow contract
        (satellite of the streams plane): fold the parked dropped-lane
        counts (ONE batched transfer for all parked checks) and
        re-expand EXACTLY the dropped source lanes — their subscriber
        deliveries enqueue with the ORIGINAL inject stamp, so the
        latency ledger includes the redelivery wait.  Every retry round
        completes at least one parked lane (the CSR width is never
        smaller than a single lane's degree), so this converges without
        a round bound.  Returns True if redeliveries were queued."""
        if not self._fanout_checks:
            return False
        checks = self._fanout_checks
        self._fanout_checks = []
        if len(checks) == 1:
            counts = [int(host_read("fanout_drops", checks[0].count))]
        else:
            n = len(checks)
            padded = 1 << (n - 1).bit_length()
            xs = [c.count for c in checks] \
                + [np.int32(0)] * (padded - n)
            counts = np.asarray(host_read(
                "fanout_drops", _stack_counts(*xs)))[:n].tolist()
        requeued = False
        for c, cnt in zip(checks, counts):
            exp = c.expander
            exp.dropped_lanes += int(cnt)
            if cnt == 0:
                continue
            exp.redeliveries += 1
            dst, gargs, valid = exp.expand(c.keys, c.args, c.dropped)
            cnt2, dropped2 = exp.take_drop()
            self._fanout_checks.append(_FanoutCheck(
                expander=exp, dst_type=c.dst_type,
                dst_method=c.dst_method, keys=c.keys, args=c.args,
                dropped=dropped2, count=cnt2,
                inject_tick=c.inject_tick))
            self.queues[(c.dst_type, c.dst_method)].append(PendingBatch(
                args=gargs, keys_dev=dst, mask=valid,
                inject_tick=c.inject_tick))
            requeued = True
        return requeued

    def _pre_exchange_round(self, pending) -> None:
        """Exchange OVERLAP, unfused path (tensor/exchange.py): at round
        start, dispatch the cross-shard exchange for every queued batch
        whose resolution is ALREADY CACHED (injector fast path) — the
        exchange is a pure function of (rows, args, mask), independent
        of arena state, so moving tick t+1's cross traffic while the
        preceding groups' kernels still run on device is exact by
        construction.  The consuming group verifies the stamps and the
        rows identity before using the result; anything stale silently
        recomputes inline.  Clustered silos skip this (a batch may ship
        to another silo before it runs — the pre-dispatch would be
        wasted device work)."""
        exchange = self.profiler.stage("exchange").open()
        for (type_name, method), batches in pending.items():
            if len(batches) != 1:
                continue
            b = batches[0]
            if (b.future is not None or b.keys_dev is None
                    or b.keys_wide is not None or b.rows is None
                    or b.segments is not None
                    or b.pre_exchange is not None):
                continue
            arena = self.arenas.get(type_name)
            if arena is None or arena.sharding is None:
                continue
            if b.generation != arena.generation \
                    or b.epoch != arena.eviction_epoch:
                continue
            if not exchangeable_args(b.args, len(b)):
                continue
            base = b.mask if b.mask is not None else _mask_for(len(b))
            r2, a2, m2, dropped, stats, run_cost = \
                self.exchange.dispatch(
                    arena, b.rows, b.args, base,
                    site=(type_name, method), defer_stats=True)
            b.pre_exchange = (r2, a2, m2, dropped, stats,
                              arena.generation, arena.eviction_epoch,
                              b.rows, time.perf_counter(), run_cost)
        exchange.close()

    def _drain_exchange_checks(self) -> bool:
        """Quiescence half of the cross-shard exchange: fold the parked
        device stat vectors (ONE batched transfer for all parked checks,
        same discipline as the miss counters) and re-deliver any
        bucket-overflow lanes through the exact path with their original
        inject stamps.  Returns True if redeliveries were queued."""
        if not self._exchange_checks:
            return False
        checks = self._exchange_checks
        self._exchange_checks = []
        if len(checks) == 1:
            stats = np.asarray(host_read("exchange_stats",
                                         checks[0].stats))[None, :]
        else:
            n = len(checks)
            padded = 1 << (n - 1).bit_length()
            width = int(checks[0].stats.shape[0])
            xs = [c.stats for c in checks] \
                + [np.zeros(width, np.int32)] * (padded - n)
            stats = np.asarray(host_read("exchange_stats",
                                         _stack_counts(*xs)))[:n]
        xch = self.exchange
        requeued = False
        for c, row in zip(checks, stats):
            if xch is not None:
                # the demand tail sizes future caps for THIS site —
                # occupancy-sized buckets (tensor/exchange.py)
                xch.fold_stats(row, site=(c.type_name, c.method),
                               scale=c.scale, width=c.width)
            if c.measure_only or int(row[1]) == 0:
                continue
            if xch is not None:
                xch.redeliveries += 1
            # no_fanout: the original pass already expanded subscriber
            # deliveries for these lanes (expansion gates on RESOLUTION,
            # which succeeded — the drop happened downstream, in the
            # bucket); re-expanding would double-deliver
            self.queues[(c.type_name, c.method)].append(PendingBatch(
                args=c.args, keys_dev=c.keys, mask=c.dropped,
                no_fanout=True, inject_tick=c.inject_tick))
            requeued = True
        return requeued

    # -- group execution ----------------------------------------------------

    @staticmethod
    def _coalesce_host_batches(batches: List[PendingBatch]
                               ) -> List[PendingBatch]:
        """Merge CONSECUTIVE runs of plain host-key batches (no cached
        rows, no futures, no masks) into one numpy batch per run before
        resolution.

        Cross-silo slab arrivals queue one such batch per slab; without
        merging, each distinct coalescing pattern produces a distinct
        concatenated batch size and a fresh XLA compile — measured as THE
        dominant cost of the cross-silo presence run (2.2s of a 3.2s run
        compiling).  One merged batch pads to a stable bucket instead.
        Only adjacent batches merge, so FIFO application order against
        non-mergeable batches in the same round is preserved (matters for
        last-writer-wins handlers)."""

        def mergeable(b: PendingBatch) -> bool:
            return (b.future is None and b.keys_host is not None
                    and b.rows is None and b.keys_dev is None
                    and b.mask is None and not b.no_fanout)

        def merge(member: List[PendingBatch]) -> PendingBatch:
            def cat(*leaves):
                return np.concatenate(
                    [np.broadcast_to(np.asarray(x),
                                     (len(member[i].keys_host),)
                                     + np.shape(x)[1:])
                     if np.ndim(x) == 0 else np.asarray(x)
                     for i, x in enumerate(leaves)])

            return PendingBatch(
                args=jax.tree_util.tree_map(cat,
                                            *(b.args for b in member)),
                keys_host=np.concatenate([b.keys_host for b in member]))

        out: List[PendingBatch] = []
        r = 0
        while r < len(batches):
            if not mergeable(batches[r]):
                out.append(batches[r])
                r += 1
                continue
            run_end = r
            while run_end < len(batches) and mergeable(batches[run_end]):
                run_end += 1
            run = batches[r:run_end]
            out.append(run[0] if len(run) == 1 else merge(run))
            r = run_end
        return out

    def _filter_ownership(self, type_name: str, method: str,
                          batches: List[PendingBatch]
                          ) -> List[PendingBatch]:
        """Resolve-time ownership re-check for host-key batches.

        Ownership proven at ENQUEUE time can be stale by DRAIN time (a
        ring change between the two evicts the keys via handoff); blindly
        re-resolving would re-activate them here while the new owner also
        activates them — a duplicate activation.  Strays found now are
        shipped (or, for result-carrying batches, the whole batch is
        re-routed and its future chained).  Single-member rings
        short-circuit inside partition(), so the single-silo hot path
        pays one cheap call."""
        arena = self.arenas.get(type_name)
        gen = arena.generation if arena is not None else -1
        epoch = arena.eviction_epoch if arena is not None else -1
        out: List[PendingBatch] = []
        for b in batches:
            if b.keys_host is None:
                out.append(b)  # device keys: the miss path owns routing
                continue
            if b.rows is not None and b.generation == gen \
                    and b.epoch == epoch:
                # injector fast path: rows resolved under this generation
                # AND eviction epoch — handoff evicts strays by bumping
                # the epoch (rows stay put), so still-valid rows imply
                # still-owned keys
                out.append(b)
                continue
            local_mask, remote = self.router.partition(type_name,
                                                       b.keys_host)
            if not remote:
                out.append(b)
                continue
            if b.future is not None:
                # results are positional over the full batch — re-route
                # the whole thing and chain the caller's future
                routed = self.router.route_batch(
                    type_name, method, b.keys_host, b.args,
                    want_results=True)

                def relay(f: asyncio.Future, dst=b.future) -> None:
                    if dst.done():
                        return
                    if f.exception() is not None:
                        dst.set_exception(f.exception())
                    else:
                        dst.set_result(f.result())

                routed.add_done_callback(relay)
                continue
            args_h = jax.tree_util.tree_map(np.asarray, b.args)
            for target, ridx in remote.items():
                self.router.ship_slab(
                    target, type_name, method, b.keys_host[ridx],
                    jax.tree_util.tree_map(
                        lambda a: a if np.ndim(a) == 0 else a[ridx],
                        args_h))
            lidx = np.nonzero(local_mask)[0]
            if len(lidx):
                out.append(PendingBatch(
                    args=jax.tree_util.tree_map(
                        lambda a: a if np.ndim(a) == 0 else a[lidx],
                        args_h),
                    keys_host=b.keys_host[lidx],
                    no_fanout=b.no_fanout,
                    inject_tick=b.inject_tick))
        return out

    def _route_group(self, type_name: str, method: str,
                     batches: List[PendingBatch]) -> List[PendingBatch]:
        """Clustered pre-pass of one (type, method) group, run BEFORE
        fan-out expansion: ship non-owned partitions (ownership re-check)
        and park fence-deferred batches.  Ordering matters — a batch the
        handoff fence defers must defer WITH its fan-out unexpanded, or
        subscriber deliveries would apply a full tick before the source
        grain's own update (and a tick-boundary checkpoint between the
        two would persist the subscriber effects without the source
        update).  The deferred batch re-queues at tick end with fan-out
        still enabled, so source update and subscriber deliveries land
        in the SAME later tick."""
        arena = self.arena_for(type_name)
        batches = self._filter_ownership(type_name, method, batches)
        if batches and not self.router.handoff_settled():
            # handoff fence: host-key batches touching UNSEEN keys
            # would activate them from the store, racing the previous
            # owner's write-back — defer those until peers release
            # (or the fence times out); everything else flows
            safe: List[PendingBatch] = []
            for b in batches:
                if b.keys_host is not None and (
                        b.rows is None or b.generation != arena.generation
                        or b.epoch != arena.eviction_epoch):
                    _, found = arena.lookup_rows(b.keys_host)
                    if not found.all():
                        # park in a side list (re-queued at tick end) so
                        # the round loop doesn't re-examine it every
                        # round of this tick
                        self._fence_deferred.append(
                            ((type_name, method), b))
                        continue
                safe.append(b)
            batches = safe
        return batches

    def _run_group(self, type_name: str, method: str,
                   batches: List[PendingBatch]) -> None:
        """Execute one (type, method) group.

        Latency discipline: the steady-state path (one device-resident
        batch of a stable size) performs ZERO eager device ops — one jitted
        resolve (emit batches) + one jitted step.  Eager jax ops measured
        ~1000× a jit dispatch on the pre-PR-1 chip rig, so host-side
        batches are padded in numpy and device batches are compiled at
        their natural (stable) sizes instead of being padded to
        buckets."""
        seg_batches = [b for b in batches if b.segments is not None]
        if seg_batches:
            # pull-mode stream deliveries execute one-by-one (their
            # lanes are pre-grouped by destination row against a
            # specific layout stamp — merging or exchanging them would
            # destroy the row alignment the scatter-free reductions
            # rely on); ordinary batches in the same group keep the
            # standard path below
            for b in seg_batches:
                self._run_segments_batch(type_name, method, b)
            batches = [b for b in batches if b.segments is None]
            if not batches:
                return
        info = vector_type(type_name)
        arena = self.arena_for(type_name)
        prof = self.profiler
        resolve = prof.stage("resolve").open()
        if self._span_recorder() is not None:
            # tick-span accounting BEFORE coalescing (the merge keeps the
            # payloads but not the per-batch trace contexts)
            total = 0
            for b in batches:
                if b.trace is not None:
                    self._tick_traces.append(b.trace)
                total += len(b)
            self._tick_counts[f"{type_name}.{method}"] += total
        # cross-shard exchange pre-check (tensor/exchange.py): a group is
        # an exchange candidate when every batch carries device keys (the
        # redelivery address for bucket-overflow lanes) and no futures
        # (the exchange permutes lanes, which would destroy positional
        # results).  Final eligibility also needs every RESOLUTION to be
        # device-side — checked after resolve; ledger accounting for
        # candidates moves past that decision so dropped lanes are never
        # counted before they deliver.
        maybe_exchange = (
            self._exchange_live() and self.exchange.engaged()
            and arena.sharding is not None
            and all(b.future is None and b.keys_dev is not None
                    and b.keys_wide is None for b in batches))
        ledger = self.ledger
        if ledger.enabled and not maybe_exchange:
            # latency ledger, host-resolved side: injector/host-key
            # batches always fully deliver (host resolution activates),
            # so their accounting is one numpy scalar add per batch —
            # recorded BEFORE coalescing (the merge drops per-batch
            # inject stamps).  Device-key batches are recorded after
            # resolution below, masked to the lanes actually applied.
            for b in batches:
                if b.inject_tick >= 0 and (b.keys_host is not None
                                           or b.rows is not None):
                    ledger.record_host(type_name, method,
                                       self.tick_number - b.inject_tick,
                                       len(b))
        batches = self._coalesce_host_batches(batches)

        # re-resolve if any batch's resolution itself grew/repacked the
        # arena (growth is rare; the loop converges immediately after)
        while True:
            gen0 = arena.generation
            resolved = [self._resolve_batch(arena, b, method)
                        for b in batches]
            if arena.generation == gen0:
                break
        fan = self._fanouts.get((type_name, method))
        if fan is not None:
            self._expand_resolved_fanout(fan, batches, resolved)
        route = self._stream_routes.get((type_name, method))
        if route is not None and self._streams_live():
            self._expand_resolved_stream_routes(route, type_name, method,
                                                batches, resolved)
        # final exchange eligibility: every resolution stayed on device
        # (a stale injector falls back to host re-resolution — np rows —
        # and the group takes the legacy path this round) and every
        # batch's args are lane-aligned (slab-style handlers consuming a
        # whole buffer per tick cannot have their rows permuted away
        # from the buffer)
        will_exchange = maybe_exchange and not any(
            isinstance(r, np.ndarray) for r, _ in resolved) and all(
            exchangeable_args(b.args, len(b)) for b in batches)
        if ledger.enabled and not will_exchange:
            # latency ledger, device side: count exactly the lanes the
            # step will apply (mask ∧ resolved, combined INSIDE the jit)
            # — unresolved misses are counted when their redelivery
            # applies (original stamp), never twice.  One async jit
            # dispatch per device batch; nothing crosses to the host.
            for b, (rows, _a) in zip(batches, resolved):
                if b.inject_tick < 0:
                    continue
                if maybe_exchange:
                    # exchange candidate that fell back this round: the
                    # pre-coalesce host-side record was skipped above —
                    # account the batch by its actual resolution kind
                    if isinstance(rows, np.ndarray):
                        ledger.record_host(
                            type_name, method,
                            self.tick_number - b.inject_tick, len(b))
                        continue
                elif b.keys_host is not None or b.rows is not None:
                    continue
                base = b.mask if b.mask is not None \
                    else _mask_for(len(b))
                ledger.record_rows(type_name, method,
                                   self.tick_number - b.inject_tick,
                                   rows, base)
        masks = [b.mask for b in batches]
        if len(resolved) == 1:
            rows, args = resolved[0]
            mask = masks[0]
        else:
            # multi-batch rounds are rare (fan-in of emits from several
            # producer groups); one eager concat per input
            rows = jnp.concatenate([jnp.asarray(r) for r, _ in resolved])
            args = jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(
                    [jnp.broadcast_to(jnp.asarray(x),
                                      (len(resolved[i][0]),)
                                      + jnp.shape(x)[1:])
                     if jnp.ndim(x) == 0 else jnp.asarray(x)
                     for i, x in enumerate(xs)]),
                *(a for _, a in resolved))
            mask = None if all(m is None for m in masks) else \
                jnp.concatenate([m if m is not None
                                 else jnp.ones(len(b), dtype=bool)
                                 for m, b in zip(masks, batches)])

        if isinstance(rows, np.ndarray):
            # host batch: pad in numpy (cheap) to a bucket for compile reuse
            m_real = len(rows)
            bucket = self._bucket_for(m_real)
            if bucket != m_real:
                rows = np.concatenate(
                    [rows, np.full(bucket - m_real, -1, np.int32)])
                args = jax.tree_util.tree_map(
                    lambda a: _pad_np(np.asarray(a), bucket), args)
            mask_np = np.zeros(bucket, bool)
            mask_np[:m_real] = True
            mask = mask_np
            m_total = m_real
        else:
            m_total = rows.shape[0]

        self.messages_processed += m_total
        want_results = any(b.future is not None for b in batches)
        resolve.close()

        if (self._exchange_live() and not self.exchange.engaged()
                and arena.sharding is not None
                and not isinstance(rows, np.ndarray)
                and all(b.future is None and b.keys_dev is not None
                        and b.keys_wide is None for b in batches)
                and all(exchangeable_args(b.args, len(b))
                        for b in batches)):
            # DISENGAGED exchange (identity — tensor/exchange.py): the
            # batch delivers through the implicit-collective path, but
            # every Nth ELIGIBLE group — same eligibility as the
            # engaged path, so the sampled counters estimate exactly
            # the traffic the structured formulation would carry —
            # runs a measure-only classification, keeping the
            # cross-traffic counters and occupancy estimates honest at
            # 1/N of the classification cost
            xch = self.exchange
            interval = max(1, self.config.exchange_probe_interval)
            scale = xch.probe_scale((type_name, method), interval)
            if scale:
                base = mask if mask is not None \
                    else _mask_for(rows.shape[0])
                self._exchange_checks.append(_ExchangeCheck(
                    type_name=type_name, method=method, keys=None,
                    args=None, dropped=None,
                    stats=xch._probe(arena, rows, base,
                                     (type_name, method)),
                    width=int(rows.shape[0]),
                    measure_only=True, scale=scale))

        exchanged = False
        if will_exchange and not isinstance(rows, np.ndarray):
            # cross-shard exchange (tensor/exchange.py): bucket by
            # destination shard + one all_to_all, so the step kernel's
            # scatters land shard-local.  The dropped mask + stats stay
            # on device, parked like a miss-check; messages_processed
            # already counted the LOGICAL lanes above (the exchanged
            # width is a padded transport shape, not traffic).
            xst = prof.stage("exchange").open()
            keys_cat = batches[0].keys_dev if len(batches) == 1 \
                else jnp.concatenate([b.keys_dev for b in batches])
            base = mask if mask is not None \
                else _mask_for(rows.shape[0])
            orig_args = args
            width = int(rows.shape[0])
            pre = batches[0].pre_exchange if len(batches) == 1 else None
            if pre is not None and pre[5] == arena.generation \
                    and pre[6] == arena.eviction_epoch \
                    and rows is pre[7]:
                # exchange overlap: the round-start pre-dispatch already
                # moved this batch's cross traffic — its all_to_all ran
                # under the preceding groups' compute.  The credit is
                # the wall the device had to hide it in; the deferred
                # run counters fold now (a consumed pre-dispatch IS the
                # batch's one exchange).
                rows, args, mask, dropped, stats = pre[:5]
                self.exchange.fold_dispatch(pre[9])
                self.exchange.note_overlap(time.perf_counter() - pre[8])
            else:
                if pre is not None:
                    # stale pre-dispatch: its counters were deferred
                    # and are dropped with it — the inline recompute
                    # below is the batch's one counted exchange
                    self.exchange.pre_discards += 1
                rows, args, mask, dropped, stats = self.exchange.dispatch(
                    arena, rows, args, base, site=(type_name, method))
            if len(batches) == 1:
                batches[0].pre_exchange = None
            # the ORIGINAL inject stamp rides the check: overflow lanes
            # redeliver with it, so their recorded latency includes the
            # redelivery wait (min over the group's stamped batches —
            # conservative when a rare multi-batch group mixes ticks)
            inj = min((b.inject_tick for b in batches
                       if b.inject_tick >= 0), default=-1)
            self._exchange_checks.append(_ExchangeCheck(
                type_name=type_name, method=method, keys=keys_cat,
                args=orig_args, dropped=dropped, stats=stats,
                width=width, inject_tick=inj))
            if ledger.enabled and inj >= 0:
                # post-exchange accounting: exactly the lanes delivered
                # this tick (dropped lanes count at redelivery)
                ledger.record_rows(type_name, method,
                                   self.tick_number - inj, rows, mask)
            exchanged = True
            xst.close()
        apply = prof.stage("apply").open()

        step = self._get_step(info, method)
        if not self._steps_donated:
            # undonated EXECUTION (donate_state off) — counted per run
            # like the fused path, matching the metric's unit; a
            # per-compile count would flatline while every tick ran
            # without double-buffering
            self.donation_fallbacks += 1
        if mask is None:
            mask = _mask_for(rows.shape[0] if hasattr(rows, "shape")
                             else len(rows))
        if self.attribution.enabled:
            # workload attribution (tensor/attribution.py): fold this
            # group's destination rows into the per-row traffic counts +
            # sketch + method slots — ONE async jit dispatch.  Rows here
            # are final (post-exchange when exchanged, so dropped lanes
            # count at their redelivery; masked miss lanes likewise),
            # which keeps the fold in lock-step with what the step
            # kernel actually applies.  The batch's keys_dev is the
            # delta-plan memo's stable identity for emit-leg batches,
            # whose rows re-resolve to a FRESH array every tick (valid
            # only unexchanged + single-batch: exchange permutes lanes
            # per tick, concat builds fresh buffers).
            ident = batches[0].keys_dev \
                if len(batches) == 1 and not exchanged else None
            self.attribution.record_group(arena, type_name, method,
                                          rows, mask, ident=ident)
        # host rows are already bucket-padded here, so len(rows) is the
        # COMPILED shape (the padding rung), not the logical batch size.
        # The arena capacity is part of the signature because the state
        # columns' shapes are the capacity — a grow retraces EVERY batch
        # shape and must be attributed, not silently skipped.  Host vs
        # device is deliberately NOT in the key: jit caches on avals, so
        # an np batch and a device batch of the same shape share one
        # compile (a host/device split would record phantom events).
        # The exchange flag IS in the key: an exchanged batch's lanes
        # are a different transport shape, and a live exchange toggle
        # re-specializing a seen (type, method, m) must be attributed
        # (cause cross_shard), not read as organic shape churn.
        sig = (info.name, method, int(len(rows)), arena.capacity,
               exchanged)
        if sig in self._seen_steps:
            new_state, results, emits, fence = step(arena.state, rows,
                                                    args, mask)
        else:
            # first call of this input signature: jax traces + lowers +
            # compiles synchronously inside the call, so its wall time
            # IS the lowering cost — record it cause-coded
            # (tensor/profiler.py churn cause list)
            cause = self._infer_step_cause(
                info.name, method, sig, isinstance(rows, np.ndarray))
            t_compile = time.perf_counter()
            new_state, results, emits, fence = step(arena.state, rows,
                                                    args, mask)
            self.compile_tracker.record(
                cause, key=f"{info.name}.{method}[{sig[2]}]",
                seconds=time.perf_counter() - t_compile,
                tick=self.tick_number)
            self._seen_steps.add(sig)
        # buffer flip: the donated input columns are gone; the program's
        # outputs are the live state now (layout validated — donation
        # must never smuggle in a wrong-shaped column)
        arena.adopt_state(new_state)
        self._tick_fence = fence
        if not isinstance(rows, np.ndarray):
            # device-routed batches (injector fast path, emit hits) never
            # cross to the host, so record their traffic on the device-side
            # use clock — otherwise collection would evict hot rows
            arena.touch_rows_dev(rows, self.tick_number)
        apply.close()
        with prof.stage("route"):
            self._route_emits(emits)
        if want_results:
            with prof.stage("results"):
                self._deliver_results(batches, results)

    def _run_segments_batch(self, type_name: str, method: str,
                            b: PendingBatch) -> None:
        """Execute one pull-mode stream delivery (tensor/streams_plane
        .py): lanes are pre-grouped by destination arena row with
        row-aligned offsets, so the step's fan-in reductions run
        scatter-free and there is NOTHING to resolve — the rows were
        baked by the adjacency build and are exactly valid while the
        arena's (generation, eviction_epoch) stamps hold.  A stale
        batch (rows moved/freed between enqueue and execution) falls
        back to key-addressed delivery: the push path's device
        resolution re-activates evicted subscribers through the miss
        machinery, preserving the at-least-once contract."""
        arena = self.arena_for(type_name)
        if b.generation != arena.generation \
                or b.epoch != arena.eviction_epoch:
            self.queues[(type_name, method)].append(PendingBatch(
                args=b.args, keys_dev=b.keys_dev, mask=b.mask,
                inject_tick=b.inject_tick))
            return
        info = vector_type(type_name)
        prof = self.profiler
        resolve = prof.stage("resolve").open()
        m = len(b)
        if self._span_recorder() is not None:
            if b.trace is not None:
                self._tick_traces.append(b.trace)
            self._tick_counts[f"{type_name}.{method}"] += m
        if self.ledger.enabled and b.inject_tick >= 0:
            # one collapsed-kernel dispatch: every lane shares the
            # batch's delta, mask combined inside the jit
            self.ledger.record_rows(type_name, method,
                                    self.tick_number - b.inject_tick,
                                    b.rows, b.mask)
        if self.attribution.enabled:
            # the adjacency's edge arrays are identity-stable across
            # ticks (same build → same buffers), so the delta-plan memo
            # applies buffered k·delta folds — near-zero steady cost
            self.attribution.record_group(arena, type_name, method,
                                          b.rows, b.mask,
                                          ident=b.keys_dev)
        self.messages_processed += m
        resolve.close()
        apply = prof.stage("apply").open()
        step = self._get_step(info, method)
        if not self._steps_donated:
            self.donation_fallbacks += 1
        sig = (info.name, method, m, arena.capacity, "seg")
        if sig in self._seen_steps:
            new_state, results, emits, fence = step(
                arena.state, b.rows, b.args, b.mask, b.segments)
        else:
            cause = self._infer_step_cause(info.name, method, sig, False)
            t_compile = time.perf_counter()
            new_state, results, emits, fence = step(
                arena.state, b.rows, b.args, b.mask, b.segments)
            self.compile_tracker.record(
                cause, key=f"{info.name}.{method}[seg:{m}]",
                seconds=time.perf_counter() - t_compile,
                tick=self.tick_number)
            self._seen_steps.add(sig)
        arena.adopt_state(new_state)
        self._tick_fence = fence
        # collection liveness: a dense elementwise touch over the rows
        # holding edges (the offsets know them) — never a lane-sized
        # scatter-max on this path
        arena.touch_rows_dense(b.segments, self.tick_number)
        apply.close()
        with prof.stage("route"):
            self._route_emits(emits)

    def _deliver_results(self, batches: List[PendingBatch],
                         results: Any) -> None:
        start = 0
        for b in batches:
            m = len(b)
            if b.future is not None and not b.future.done():
                if results is None:
                    b.future.set_result(None)
                else:
                    # d2h only here — the caller explicitly asked
                    b.future.set_result(jax.tree_util.tree_map(
                        lambda x: np.asarray(host_read(
                            "results", x[start:start + m])), results))
            start += m

    def _route_emits(self, emits) -> None:
        if not emits:
            return
        for emit in (emits if isinstance(emits, (tuple, list)) else (emits,)):
            if emit is None:
                continue
            keys = emit.keys
            if isinstance(keys, tuple):
                # wide destination: (hi, lo) int32 word pair
                hi, lo = (k if (isinstance(k, jnp.ndarray)
                                and k.dtype == jnp.int32)
                          else jnp.asarray(k, jnp.int32) for k in keys)
                self.queues[(emit.interface, emit.method)].append(
                    PendingBatch(args=emit.args, keys_wide=(hi, lo),
                                 mask=emit.mask,
                                 inject_tick=self.tick_number))
                continue
            if not (isinstance(keys, jnp.ndarray) and keys.dtype == jnp.int32):
                keys = jnp.asarray(keys, dtype=jnp.int32)
            self.queues[(emit.interface, emit.method)].append(PendingBatch(
                args=emit.args, keys_dev=keys, mask=emit.mask,
                inject_tick=self.tick_number))

    # ================= compilation ========================================

    def _infer_step_cause(self, type_name: str, method: str,
                          sig: Tuple, is_host: bool) -> str:
        """Name the cause of a first-seen step-call signature (the churn
        cause list in tensor/profiler.py): a (type, method, m) the last
        reshard forgot recompiles BECAUSE of the reshard; a batch shape
        already seen under a DIFFERENT arena capacity recompiles because
        the arena grew/repacked (state column shapes ARE the capacity);
        a never-seen (type, method) is genuinely new; a host batch above
        every rung seen for its method grew the padding bucket; a seen
        shape re-specializing under the OTHER cross-shard-exchange flag
        is the exchange toggle; anything else is a new batch shape."""
        _t, _m, m, _cap, xch = sig
        if xch == "seg":
            # pull-mode stream deliveries: their lane count is the edge
            # count, disjoint from the exchange cause list — a same-shape
            # recompile under a new capacity is still a repack, a fresh
            # shape is organic (adjacency rebuild changed the edge set)
            seen_seg = [s for s in self._seen_steps
                        if s[0] == type_name and s[1] == method
                        and s[4] == "seg"]
            if any(s[2] == m for s in seen_seg):
                return CAUSE_GENERATION_REPACK
            return CAUSE_NEW_METHOD if not seen_seg \
                else CAUSE_SHAPE_CHANGE
        if (type_name, method, m) in self._reshard_forgotten:
            self._reshard_forgotten.discard((type_name, method, m))
            return CAUSE_MESH_RESHARD
        if (type_name, method, m) in self._toggle_forgotten:
            # a live donate_state toggle dropped the compiled steps:
            # recompiles of signatures it forgot are caused by the
            # toggle, not by organic traffic shapes
            self._toggle_forgotten.discard((type_name, method, m))
            return CAUSE_CONFIG_TOGGLE
        seen_method = [s for s in self._seen_steps
                       if s[0] == type_name and s[1] == method]
        if not seen_method:
            return CAUSE_NEW_METHOD
        if any(s[2] == m and s[4] == xch for s in seen_method):
            # same batch shape + exchange flag, different capacity: the
            # arena repacked
            return CAUSE_GENERATION_REPACK
        if (xch or not is_host) \
                and xch not in {s[4] for s in seen_method}:
            # first compile of this method under the OTHER exchange
            # flag: the toggle re-specialized it (exchanged widths are
            # padded transport shapes, so the lane count changes too —
            # without this check the toggle would read as organic shape
            # churn).  Host batches never exchange by design, so an
            # unexchanged HOST compile for an exchanged-only method is
            # organic traffic, not a toggle.
            return CAUSE_CROSS_SHARD
        if is_host and m > max(s[2] for s in seen_method):
            return CAUSE_BUCKET_GROWTH
        return CAUSE_SHAPE_CHANGE

    def _bucket_for(self, m: int) -> int:
        for b in self.config.bucket_sizes:
            if m <= b:
                return b
        # beyond the ladder: round up to a multiple of the last rung so
        # oversized batches still share compiles (never pad SHORTER than
        # m — that would corrupt the batch)
        last = self.config.bucket_sizes[-1]
        return -(-m // last) * last

    def _get_step(self, info: VectorGrainInfo, method: str) -> Callable:
        donate = self.config.donate_state
        if donate != self._steps_donated:
            # live donation toggle: the compiled steps baked the other
            # donation mode — drop them and attribute the recompiles to
            # the toggle (the _reshard_forgotten discipline)
            self._steps_donated = donate
            self._step_cache.clear()
            self._toggle_forgotten |= {(s[0], s[1], s[2])
                                       for s in self._seen_steps}
            self._seen_steps = set()
        key = (info.name, method)
        step = self._step_cache.get(key)
        if step is not None:
            return step
        handler = info.handlers[method]

        def step_fn(state, rows, args, mask, *segments):
            n_rows = next(iter(state.values())).shape[0]
            # a lane whose key missed optimistic resolution (row -1) is
            # redelivered once its grain activates: this delivery must
            # not reach the handler's emits or results either
            mask = mask & (rows >= 0)
            # named_scope labels the HLO for jax.profiler deep captures
            # (tensor/profiler.py) — trace-time only, zero runtime cost
            with jax.named_scope(f"orleans.dispatch.{info.name}.{method}"):
                out = handler(state,
                              Batch(rows=rows, args=args, mask=mask,
                                    segments=segments[0] if segments
                                    else None),
                              n_rows)
            # normalize handler returns: state | (state,) | (state, results)
            # | (state, results, emits)
            if isinstance(out, dict):
                state2, results, emits = out, None, ()
            else:
                out = tuple(out)
                state2 = out[0]
                results = out[1] if len(out) > 1 else None
                emits = out[2] if len(out) > 2 else ()
            # the completion FENCE: a 1-lane output derived from the new
            # state.  The pipeline's event-driven completion blocks on
            # THIS, never on the state columns — the next tick donates
            # those away while the fence (its own tiny output buffer)
            # stays valid for the waiting executor thread.
            first = jax.tree_util.tree_leaves(state2)[0]
            fence = jnp.reshape(first, (-1,))[:1]
            return state2, results, emits, fence

        step = jax.jit(step_fn, donate_argnums=(0,) if donate else ())
        self._step_cache[key] = step
        return step

    # ================= stats ==============================================

    def compile_count(self) -> int:
        """Total step-program compilations (one per distinct input shape
        per (type, method)).  The cross-silo health number: un-merged
        slab arrivals show up here as churn — BENCH measured compile time
        as THE dominant cost of the un-coalesced cross-silo run."""
        total = 0
        for step in self._step_cache.values():
            size = getattr(step, "_cache_size", None)
            if size is None:
                continue
            try:
                total += int(size())
            except Exception:  # noqa: BLE001 — jax-version-specific API
                pass
        return total

    def snapshot(self) -> Dict[str, Any]:
        return {
            "compiles": self.compile_count(),
            "ticks": self.ticks_run,
            "rounds": self.rounds_run,
            "messages": self.messages_processed,
            "tick_seconds": self.tick_seconds,
            "msgs_per_sec": (self.messages_processed / self.tick_seconds
                             if self.tick_seconds > 0 else 0.0),
            "activation_passes": self.activation_passes,
            "stages": dict(self.profiler.stage_seconds),
            "last_tick_stages": dict(self.profiler.tick_stages),
            "tick_latency": self.latency_stats(),
            # continuous pipelined ticking: in-flight window, completion
            # events, overlap credit, donation fallbacks
            "pipeline": self.pipeline.snapshot(),
            "autofuse": self.autofuser.snapshot(),
            "arenas": {name: a.live_count for name, a in self.arenas.items()},
            # host key→row lookups per arena, by path (arena.lookup_rows)
            "lookup_keys": {name: {"dense_lookup_keys": a.dense_lookup_keys,
                                   "sorted_lookup_keys":
                                       a.sorted_lookup_keys}
                            for name, a in self.arenas.items()},
            "evicted": sum(a.evicted_count for a in self.arenas.values()),
            "restored": sum(a.restored_count for a in self.arenas.values()),
            # live migration (migrate_keys): batched moves + grains
            # moved + per-arena placement pins still active
            "migrations": self.migrations,
            "grains_migrated": self.grains_migrated,
            "migration_pins": {name: len(a._shard_override)
                               for name, a in self.arenas.items()
                               if a._shard_override},
            # hot-grain replication (replicate_key/demote_key)
            "replications": self.replications,
            "grains_replicated": self.grains_replicated,
            "replica_demotions": self.replica_demotions,
            "replica_folds": sum(a.replica_folds
                                 for a in self.arenas.values()),
            "replicated_now": sum(len(a._replicas)
                                  for a in self.arenas.values()),
            "collection": self.collector.snapshot(),
            "fragmentation": {name: round(a.fragmentation(), 4)
                              for name, a in self.arenas.items()},
            # cross-shard routing plane (tensor/exchange.py); None off-mesh
            "exchange": self.exchange.snapshot()
            if self.exchange is not None else None,
            # device streams plane (tensor/streams_plane.py); {} when no
            # subscription route is registered
            "streams": {f"{t}.{m}": r.snapshot()
                        for (t, m), r in self._stream_routes.items()},
            # registered DeviceFanouts (tensor/fanout.py): expansion width,
            # sized vs full-width rounds, needed vs expanded lanes
            "fanouts": {f"{t}.{m}": fan.snapshot()
                        for (t, m), (fan, _, _) in self._fanouts.items()},
            # ledger health only (no device transfer here — the bucket
            # counts come from engine.ledger.snapshot(), which pays the
            # ONE d2h fetch explicitly)
            "latency_ledger": self.ledger.stats(),
            # attribution plane health only (HotSet/skew come from
            # engine.attribution.snapshot(), same explicit-d2h contract)
            "attribution": self.attribution.stats(),
            # the device cost plane: tick-phase breakdown, cause-coded
            # compile churn (the attributed replacement for the bare
            # "compiles" int above), HBM by owner + headroom
            "phases": self.profiler.snapshot(),
            "compile_attribution": self.compile_tracker.snapshot(),
            "memory": self.memledger.snapshot(),
            # device timers plane (tensor/timers_plane.py): armed/fired
            # counters + harvest width/lateness, all host mirrors
            "timers": self.timers.snapshot(),
            # durable state plane (tensor/checkpoint.py): checkpoint /
            # journal health + the committed-recovery-point age
            "durability": self.checkpointer.snapshot(),
        }


class BatchInjector:
    """Cached-destination injection: the steady-state client edge.

    Resolves the key set once (host directory), keeps the row vector on
    device, and thereafter every ``inject`` is pure h2d of payload (or zero
    transfer if args are produced on device)."""

    def __init__(self, engine: TensorEngine, type_name: str, method: str,
                 keys: np.ndarray) -> None:
        self.engine = engine
        self.type_name = type_name
        self.method = method
        self.keys = keys
        self._arena = engine.arena_for(type_name)
        # device mirror of the key set: lets registered fan-outs expand
        # injected batches with zero per-inject host→device transfer
        self._keys_dev = jnp.asarray(keys.astype(np.int32)) \
            if len(keys) and keys.max() < KEY_SENTINEL and keys.min() >= 0 \
            else None
        self.rows = None
        self._rows_host = None  # host mirror for cheap epoch revalidation
        self.generation = -2
        self.epoch = -2
        # overlapped h2d (stage()): the next injection's device-staged
        # slab + an identity-memoized np→device cache so a loader
        # reusing the same payload array keeps LEAF IDENTITY stable
        # (auto-fusion's static/per-tick split keys on it)
        self._staged: Optional[Any] = None
        self._stage_cache: Dict[int, Tuple[Any, Any]] = {}
        self._refresh()
        self.n = len(keys)

    def _refresh(self) -> None:
        arena = self._arena
        router = self.engine.router
        if router is not None and not router.handoff_settled():
            _, found = arena.lookup_rows(self.keys)
            if not found.all():
                # handoff fence: eagerly activating unseen keys here could
                # read the store before the previous owner's write-back.
                # Defer the row cache — inject() falls back to keys_host
                # batches, which the engine fences (and resolves) at drain
                self.rows = None
                self.generation = -2  # never matches: retry next inject
                return
        if (self.rows is not None and self.generation == arena.generation
                and self.epoch != arena.eviction_epoch):
            # epoch-only staleness: rows were FREED somewhere in the
            # arena but none moved.  If every cached key still resolves
            # to ITS CACHED ROW, the cached device rows are exactly
            # right — one host searchsorted + compare re-validates, no
            # device transfer, no re-resolution storm (THE 4M-eviction
            # cost this free-list path removes).  Liveness alone is NOT
            # enough: a key evicted and later re-activated lands in a
            # different slot (its old one may now hold another grain),
            # so the rows must match, not just exist.
            rows, found = arena.lookup_rows(self.keys)
            if found.all() and np.array_equal(rows, self._rows_host):
                self.epoch = arena.eviction_epoch
                return
        rows = arena.resolve_rows(self.keys, tick=self.engine.tick_number)
        # the host mirror stays UNSPREAD (lookup_rows resolves to
        # primaries, so the epoch revalidation above compares apples to
        # apples); only the device rows take the replica spread.  Any
        # promote/demote bumps the generation, so spread rows never
        # survive a replication change through the epoch-only fast path.
        self._rows_host = rows.astype(np.int32)
        if arena._replicas:
            rows = arena.spread_rows_host(rows)
        self.rows = jnp.asarray(rows)
        self.generation = arena.generation
        self.epoch = arena.eviction_epoch

    def stage(self, args: Any) -> Any:
        """Overlapped h2d: start copying the NEXT injection's payload to
        device NOW (async ``jax.device_put``), so the transfer rides
        under the current tick's device execution instead of
        serializing before the next dispatch.  ``inject()`` (with no
        args) then enqueues the staged slab with zero h2d on the
        dispatch path; the ledger's ``inject_tick`` stamp is applied at
        inject time — staging moves bytes, not the message's logical
        arrival.  Repeated stagings of the SAME numpy array reuse one
        device copy (identity-memoized), so auto-fusion's static-leaf
        detection still sees a stable identity."""
        if not self.engine.config.overlap_h2d:
            self._staged = args
            return args

        def put(a):
            if not isinstance(a, np.ndarray) or a.ndim == 0:
                return a
            ent = self._stage_cache.get(id(a))
            if ent is not None and ent[0]() is a \
                    and np.array_equal(a, ent[2]):
                # identity alone is not enough: a loader mutating the
                # SAME buffer in place between stagings must get a
                # fresh upload, not the first staging's contents — the
                # host memcmp is cheaper than the h2d it avoids on the
                # unchanged steady state
                return ent[1]
            dev = jax.device_put(a)
            try:
                ref = weakref.ref(a)
            except TypeError:
                return dev  # non-weakrefable subclass: no memo
            while len(self._stage_cache) >= 32:
                self._stage_cache.pop(next(iter(self._stage_cache)))
            self._stage_cache[id(a)] = (ref, dev, a.copy())
            return dev

        self._staged = jax.tree_util.tree_map(put, args)
        return self._staged

    def inject(self, args: Any = None, want_results: bool = False
               ) -> Optional[asyncio.Future]:
        if args is None:
            args, self._staged = self._staged, None
            if args is None:
                raise ValueError("inject() with no args needs a staged "
                                 "slab — call stage(args) first")
        else:
            # an explicit injection supersedes any staged slab: kept
            # around, a later no-arg inject() would resurrect the stale
            # payload under a fresh inject_tick stamp
            self._staged = None
        if self.generation != self._arena.generation \
                or self.epoch != self._arena.eviction_epoch:
            # rows repacked (generation) or freed (epoch) — revalidate
            self._refresh()
        future = asyncio.get_running_loop().create_future() \
            if want_results else None
        batch = PendingBatch(args=args, rows=self.rows, future=future,
                             keys_host=self.keys, keys_dev=self._keys_dev,
                             generation=self.generation, epoch=self.epoch,
                             inject_tick=self.engine.tick_number)
        if (self.type_name, self.method) in self.engine._journal_sites:
            # journaled ingress (tensor/checkpoint.py): write-ahead ring
            # append before the batch can execute
            self.engine.checkpointer.journal_ingress(
                self.type_name, self.method, batch)
        self.engine.queues[(self.type_name, self.method)].append(batch)
        self.engine._wake_up()
        return future




def _pad_np(a: np.ndarray, n: int) -> np.ndarray:
    if a.ndim == 0:
        return a  # scalar leaves broadcast in the kernel
    if a.shape[0] == n:
        return a
    pad_width = [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, pad_width)
