"""DeviceLatencyLedger: per-message latency histograms accumulated on
the device, in device-tick units.

Why this exists (ROADMAP item 2's precondition): every host-side latency
number a BLOCKING rig can observe is floored by its completion-
observation channel (~100ms on the pre-PR-1 chip rig; the event-driven
completion path — engine.TickPipeline + samples/presence.py
measure_event_floor — is what removed that floor from the latency rig)
— a per-message, or even per-tick, blocking measurement on the dispatch
path reports the rig, not the engine.  The ledger moves the
measurement to where the traffic lives: each message is stamped with its
INJECTION tick (PendingBatch.inject_tick, set at enqueue), completion is
stamped by the tick that applies it, and the tick-delta latencies
accumulate into per-(type, method) log2-bucket histograms ON the device
— one-hot bucketing + ``segment_sum`` inside the tick, exactly the trick
that made dispatch batched (PAPER.md).  Only the small [slots, buckets]
int32 count array ever crosses device→host, at the snapshot cadence —
never per message, never per tick.

Tick→seconds conversion is the reader's job (``metrics.CATALOG`` records
the unit as ticks): multiply by a seconds-per-tick measured over a whole
run (elapsed wall / ticks run — the observation floor is paid ONCE at
the end and amortizes to nothing), with no sync-floor subtraction,
because the floor never entered the measurement.

Bucket scheme (shared with metrics.Log2Histogram, base=1): bucket 0 =
delta 0 (completed in its inject tick), bucket k = [2**(k-1), 2**k)
ticks, last bucket absorbs overflow.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from orleans_tpu.tensor.profiler import host_read

#: fixed slot capacity: 64 distinct (type, method) pairs per engine.
#: Fixing it keeps the device hist shape constant for the whole engine
#: lifetime — the accumulate kernel and any fused program baking the
#: hist in never re-trace on a new method.  64x32 int32 = 8KB ceiling.
MAX_SLOTS = 64


class SlotRegistry:
    """The (type, method) → slot map shared by every device accumulator
    keyed per method — the latency ledger's histograms and the workload
    attribution plane's traffic counters index the SAME slots, so their
    per-method rows join without a name translation layer.  Bounded at
    MAX_SLOTS (the fixed device-array dimension both planes bake into
    their compiled programs)."""

    __slots__ = ("_slots", "_names")

    def __init__(self) -> None:
        self._slots: Dict[Tuple[str, str], int] = {}
        self._names: List[Tuple[str, str]] = []

    def __len__(self) -> int:
        return len(self._names)

    def items(self):
        return self._slots.items()

    def slot_for(self, type_name: str, method: str) -> int:
        key = (type_name, method)
        slot = self._slots.get(key)
        if slot is None:
            if len(self._names) >= MAX_SLOTS:
                raise RuntimeError(
                    f"slot registry capacity ({MAX_SLOTS} distinct "
                    "(type, method) pairs) exceeded")
            slot = len(self._names)
            self._slots[key] = slot
            self._names.append(key)
        return slot


def accumulate(hist, slot, deltas, valid):
    """One batched ledger update (traceable — the fused tick program
    inlines this inside its scan): bucket every lane's tick delta
    (ceil(log2(delta+1)) — bucket 0 for delta<=0, else floor(log2)+1),
    one-hot + segment_sum the valid lanes into bucket counts, and
    scatter-add them into the slot's row."""
    n_buckets = hist.shape[1]
    d = jnp.maximum(deltas, 0).astype(jnp.float32)
    b = jnp.ceil(jnp.log2(d + 1.0)).astype(jnp.int32)
    b = jnp.minimum(b, n_buckets - 1)
    counts = jax.ops.segment_sum(valid.astype(jnp.int32), b,
                                 num_segments=n_buckets)
    return hist.at[slot].add(counts)


@partial(jax.jit, donate_argnums=(0,))
def _count_rows_kernel(hist, slot, bucket, rows, base):
    """The unfused hot path's cheap variant: a batch's lanes all share
    ONE delta (same enqueue tick, same exec tick), so the bucket is a
    host-computed scalar and the device work collapses to one masked
    count + one scalar scatter-add — no per-lane bucketing, and the
    applied-lane mask (base ∧ resolved) is computed INSIDE the jit so
    the tick path never pays an eager device op."""
    valid = base & (rows >= 0)
    return hist.at[slot, bucket].add(jnp.sum(valid.astype(jnp.int32)))


class DeviceLatencyLedger:
    """Per-engine latency ledger.

    Host-resolved batches (injector fast path, keys_host) have fully
    host-known counts, so they accumulate into a host-side mirror of the
    same bucket layout — zero device work, zero transfer.  Device-routed
    batches (emits, device-key injections) have device-resident masks;
    they accumulate on device with one jit dispatch per batch (async, no
    sync).  ``snapshot()`` merges both sides with ONE ``device_get`` of
    the whole count array (``d2h_fetches`` counts them — the
    transfer-count test in tests/test_metrics.py pins the budget)."""

    def __init__(self, n_buckets: int = 16, enabled: bool = True,
                 slots: Optional[SlotRegistry] = None) -> None:
        self.enabled = enabled
        self.n_buckets = n_buckets
        # (type, method) → slot; shareable with the attribution plane so
        # both device accumulators index the same rows
        self.slots = slots if slots is not None else SlotRegistry()
        self._hist: Optional[jnp.ndarray] = None   # [MAX_SLOTS, n_buckets]
        self._host_hist = np.zeros((MAX_SLOTS, n_buckets), dtype=np.int64)
        self._dev_dirty = False      # device hist has unfetched updates
        self.d2h_fetches = 0         # completed device→host count reads
        self.records = 0             # accumulate calls (host + device)
        self._last_fetch: Optional[np.ndarray] = None

    # -- configuration -------------------------------------------------------

    def configure(self, enabled: Optional[bool] = None,
                  n_buckets: Optional[int] = None) -> None:
        """Live-reload surface (silo.update_config re-push).  Changing
        the bucket count resets the accumulated counts (the device array
        shape is part of every compiled accumulate signature)."""
        if enabled is not None:
            self.enabled = enabled
        if n_buckets is not None and n_buckets != self.n_buckets:
            self.n_buckets = n_buckets
            self._hist = None
            self._host_hist = np.zeros((MAX_SLOTS, n_buckets),
                                       dtype=np.int64)
            self._dev_dirty = False
            self._last_fetch = None

    def reset(self) -> None:
        """Zero all counts (bench A/B segment boundaries)."""
        self._hist = None
        self._host_hist[:] = 0
        self._dev_dirty = False
        self._last_fetch = None

    def snapshot_state(self) -> Tuple[Optional[jnp.ndarray], np.ndarray,
                                      bool]:
        """Rollback point for the auto-fuser's verification chain: the
        device array reference is safe to hold because fused windows
        never donate their hist input (each run returns a NEW array),
        and no unfused record can run mid-chain (any pattern break
        settles the chain first — the same invariant the arena state
        snapshot relies on)."""
        return (self._hist, self._host_hist.copy(), self._dev_dirty)

    def restore_state(self, state: Tuple[Optional[jnp.ndarray], np.ndarray,
                                         bool]) -> None:
        """Undo every accumulation since ``snapshot_state`` — rolled-back
        fused windows' counts must vanish, or their unfused replay would
        double-count every message."""
        self._hist, self._host_hist, _ = state
        self._last_fetch = None
        # the cached fetch is gone, so a restored device hist must count
        # as unfetched even if it was clean at snapshot time — restoring
        # the saved flag with no _last_fetch would hide every device-side
        # count from fetch_counts until some later record re-dirtied it
        self._dev_dirty = self._hist is not None

    def relocate(self) -> None:
        """Fold the device counts into the host mirror and drop the
        device array — the engine calls this on reshard: the hist may
        be committed to the OLD device set (it rides fused-window
        outputs), and a mixed-device jit after a mesh change would
        reject it.  Counts survive; the next record recreates the
        array on the new device set."""
        if self._hist is not None:
            self._host_hist = self.fetch_counts()
            self._hist = None
            self._last_fetch = None
            self._dev_dirty = False

    # -- slots ---------------------------------------------------------------

    def slot_for(self, type_name: str, method: str) -> int:
        return self.slots.slot_for(type_name, method)

    def _device_hist(self) -> jnp.ndarray:
        if self._hist is None:
            self._hist = jnp.zeros((MAX_SLOTS, self.n_buckets), jnp.int32)
        return self._hist

    # -- accumulation --------------------------------------------------------

    def record_host(self, type_name: str, method: str, delta: int,
                    count: int) -> None:
        """Host-known batch: the whole accumulation is one numpy scalar
        add — no device dispatch, no transfer."""
        if not self.enabled or count <= 0 or delta < 0:
            return
        d = max(int(delta), 0)
        b = 0 if d <= 0 else min(d.bit_length(), self.n_buckets - 1)
        self._host_hist[self.slot_for(type_name, method), b] += count
        self.records += 1

    def record_rows(self, type_name: str, method: str, delta: int,
                    rows: jnp.ndarray, base: jnp.ndarray) -> None:
        """Device batch on the tick hot path: count the applied lanes
        (base ∧ rows resolved) straight into hist[slot, bucket(delta)].
        ONE jit dispatch, mask combine inside, scalar bucket on host —
        the cheapest possible per-batch accounting."""
        if not self.enabled or delta < 0:
            return
        slot = self.slot_for(type_name, method)
        d = max(int(delta), 0)
        b = 0 if d <= 0 else min(d.bit_length(), self.n_buckets - 1)
        self._hist = _count_rows_kernel(self._device_hist(),
                                        jnp.int32(slot), jnp.int32(b),
                                        rows, base)
        self._dev_dirty = True
        self.records += 1

    # -- fused-program integration -------------------------------------------

    def device_hist_in(self) -> jnp.ndarray:
        """The device accumulator handed INTO a fused window program
        (tensor/fused.py threads it through the scan; accumulation
        happens inside the compiled program — zero per-window host
        work)."""
        return self._device_hist()

    def device_hist_out(self, hist: jnp.ndarray) -> None:
        self._hist = hist
        self._dev_dirty = True

    # -- snapshots -----------------------------------------------------------

    def fetch_counts(self) -> np.ndarray:
        """Total [slots, buckets] counts, host int64.  ONE device_get for
        the whole array when the device side has unfetched updates, else
        free (the cached fetch + host mirror answer)."""
        if self._dev_dirty and self._hist is not None:
            self._last_fetch = np.asarray(
                host_read("ledger", self._hist), dtype=np.int64)
            self._dev_dirty = False
            self.d2h_fetches += 1
        dev = self._last_fetch if self._last_fetch is not None \
            else np.zeros_like(self._host_hist)
        return dev + self._host_hist

    def snapshot(self) -> Dict[str, Any]:
        """Per-(type, method) histogram snapshot with p50/p95/p99 in
        device ticks (metrics.percentile_from_counts — the same
        estimator every host histogram uses)."""
        from orleans_tpu.metrics import percentile_from_counts
        counts = self.fetch_counts()
        out: Dict[str, Any] = {}
        for (type_name, method), slot in self.slots.items():
            row = counts[slot]
            total = int(row.sum())
            if total == 0:
                continue
            out[f"{type_name}.{method}"] = {
                "counts": row.tolist(),
                "total": total,
                "p50_ticks": percentile_from_counts(row, 50),
                "p95_ticks": percentile_from_counts(row, 95),
                "p99_ticks": percentile_from_counts(row, 99),
            }
        return out

    def stats(self) -> Dict[str, Any]:
        """Cheap host-side ledger health (no transfer)."""
        return {"enabled": self.enabled, "n_buckets": self.n_buckets,
                "slots": len(self.slots), "records": self.records,
                "d2h_fetches": self.d2h_fetches,
                "accumulate_compiles": accumulate_compiles()}


def accumulate_compiles() -> int:
    """Compiled variants of the hot-path accumulate kernel (one per
    batch shape) — the compile-count half of the ledger's cost contract:
    a steady batch ladder must keep this bounded (tests assert it)."""
    size = getattr(_count_rows_kernel, "_cache_size", None)
    if size is None:
        return 0
    try:
        return int(size())
    except Exception:  # noqa: BLE001 — jax-version-specific API
        return 0
