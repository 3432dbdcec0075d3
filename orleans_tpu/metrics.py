"""MetricsRegistry: typed, catalogued, mergeable cluster metrics.

The rebuild's metrics surface grew up ad hoc: every subsystem pushed
free-string ``track_metric(name, value)`` fan-outs at the process
telemetry manager (telemetry.py), with no types, no histograms, no
cluster-wide view, and nothing stopping a dashboard from meeting a
metric name no one declared.  This module is the registry half of the
observability plane (the tracing half is orleans_tpu/spans.py):

* a **catalog** — ``CATALOG`` — is the single source of truth for every
  metric name the runtime may emit: its kind (counter/gauge/histogram),
  unit, and doc string.  The registry REFUSES unknown names, and the
  tests/test_metrics.py lint walks the source tree asserting every
  emitted literal is declared, so dashboards never meet unknown strings;
* **typed instruments** with lock-cheap updates: ``Counter`` (monotonic;
  supports mirroring an externally-accumulated total), ``Gauge`` (last
  value), and ``Log2Histogram`` (fixed log2 buckets — the same scheme the
  device latency ledger uses on-mesh, tensor/ledger.py, so host and
  device distributions merge and quantile the same way);
* **mergeable snapshots**: ``MetricsRegistry.snapshot()`` is plain JSON;
  ``merge_snapshots`` folds any number of per-silo snapshots into one
  cluster view (counters sum, histogram buckets add — associative and
  commutative, so aggregation order never changes the answer; gauges
  keep per-source values and report min/max/sum);
* **percentile estimation** from log2 buckets: p50/p95/p99 with a
  bounded relative error — an estimate always lands inside its bucket,
  and a bucket spans one octave, so the estimate is within 2x of the
  exact value (tests/test_metrics.py proves the bound on synthetic
  distributions).

Reference analog: CounterStatistic/HistogramValueStatistic groups +
SiloStatisticsManager aggregation (reference: src/Orleans/Statistics/
CounterStatistic.cs, HistogramValueStatistic.cs exponential buckets,
SiloStatisticsManager.cs:31); the catalog discipline and the cluster
merge are the rebuild's additions.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

KIND_COUNTER = "counter"
KIND_GAUGE = "gauge"
KIND_HISTOGRAM = "histogram"


@dataclass(frozen=True)
class MetricSpec:
    """One catalogued metric: the name is the identity; kind picks the
    instrument; unit and doc are what a dashboard renders."""

    name: str
    kind: str
    unit: str
    doc: str


#: the single source of truth: every metric name the runtime may emit.
CATALOG: Dict[str, MetricSpec] = {}


def declare(name: str, kind: str, unit: str, doc: str) -> MetricSpec:
    if kind not in (KIND_COUNTER, KIND_GAUGE, KIND_HISTOGRAM):
        raise ValueError(f"unknown metric kind {kind!r} for {name!r}")
    spec = MetricSpec(name, kind, unit, doc)
    existing = CATALOG.get(name)
    if existing is not None and existing != spec:
        raise ValueError(f"metric {name!r} already declared as {existing}")
    CATALOG[name] = spec
    return spec


# ---------------------------------------------------------------------------
# the catalog (grouped by emitting subsystem)
# ---------------------------------------------------------------------------

# -- dead letters (resilience.DeadLetterRing; silo.collect_metrics) ----------
declare("dead_letter.total", KIND_COUNTER, "messages",
        "terminal drops of all reasons (mirrors DeadLetterRing.total)")
for _reason in ("expired", "shed_overload", "mailbox_overflow",
                "breaker_open", "retry_budget_exhausted", "undeliverable"):
    declare(f"dead_letter.{_reason}", KIND_COUNTER, "messages",
            f"terminal drops with reason {_reason}")

# -- overload containment (limits.ShedController + resilience) ---------------
declare("overload.level", KIND_GAUGE, "ratio",
        "adaptive shed level (0 = healthy, 1 = full shed)")
declare("overload.shed_count", KIND_COUNTER, "requests",
        "requests shed by adaptive admission control")
declare("overload.breaker_fast_fails", KIND_COUNTER, "requests",
        "requests fast-failed by an open per-destination breaker")
declare("overload.retries_denied", KIND_COUNTER, "requests",
        "transient resends denied by the retry token budget")

# -- activation collection (tensor/engine.IncrementalCollector) --------------
declare("collect.pause_s", KIND_HISTOGRAM, "seconds",
        "per-slice collection pause (tick-interleaved eviction stall)")
declare("collect.pause_p99_s", KIND_GAUGE, "seconds",
        "p99 over recent collection slice pauses")
declare("collect.max_pause_s", KIND_GAUGE, "seconds",
        "worst collection slice pause since engine start")
declare("collect.rows_evicted", KIND_COUNTER, "rows",
        "activations evicted by the incremental collector")
declare("collect.sweeps_completed", KIND_COUNTER, "sweeps",
        "collection sweeps drained to completion")
declare("collect.write_back_failures", KIND_COUNTER, "chunks",
        "eviction chunks whose storage write-back failed (parked+retried)")
declare("arena.fragmentation", KIND_GAUGE, "ratio",
        "per-arena freed/high-water ratio (compaction trigger input)")

# -- cross-silo slab data plane (tensor/router.VectorRouter) -----------------
for _n, _u, _d in (
        ("slabs_shipped", "slabs", "slab frames shipped to ring owners"),
        ("messages_shipped", "messages", "messages shipped inside slabs"),
        ("slabs_received", "slabs", "slab frames received"),
        ("messages_received", "messages", "messages received inside slabs"),
        ("slabs_requeued", "slabs", "bounced slabs re-queued for retry"),
        ("messages_dropped", "messages",
         "slab messages dropped after retry budget exhaustion"),
        ("slab_fragments", "fragments",
         "pre-aggregation slab fragments offered to senders"),
        ("slab_frames", "frames", "post-aggregation wire frames sent"),
        ("slab_bounces", "slabs", "slab frames bounced by byte caps"),
        ("grains_migrated_out", "grains",
         "grains live-migrated to peer silos (placement override + "
         "adopt_grains state slab)"),
        ("grains_adopted", "grains",
         "live-migrated grains adopted from peers (state landed, no "
         "store read)"),
        ("adopt_conflicts", "grains",
         "adoption slab entries already live locally (first-writer-"
         "wins; the single-activation race surfaced, never doubled)")):
    declare(f"router.{_n}", KIND_COUNTER, _u, _d)
declare("router.slab_merge_ratio", KIND_GAUGE, "ratio",
        "fragments per wire frame (>1 = sender aggregation engaged)")

# -- batched host RPC plane (runtime/rpc.py RpcCoalescer) --------------------
declare("rpc.ingress_batch_size", KIND_GAUGE, "calls",
        "mean coalesced-window size over the last collection interval "
        "(1.0 = the plane is degenerating to per-message dispatch)")
declare("rpc.coalesce_wait_s", KIND_GAUGE, "seconds",
        "mean ingress-ring wait from submit to window execution start "
        "(the latency the batching itself adds; one event-loop "
        "iteration in steady state)")
declare("rpc.fastpath_hits", KIND_COUNTER, "calls",
        "calls executed through a pre-resolved invoke-table window "
        "(no Message object, no per-call task, no per-field codec)")
declare("rpc.fastpath_fallbacks", KIND_COUNTER, "calls",
        "coalesced calls handed back to the per-message pipeline "
        "(cold/busy/remote activation, chaos injection, shed pressure) "
        "— the general path stays the correctness net; sampling never "
        "causes a fallback (sampled traces ride the trace column)")
declare("rpc.windows", KIND_COUNTER, "windows",
        "coalesced (type, method) windows executed")
declare("rpc.expired", KIND_COUNTER, "calls",
        "coalesced calls whose per-call TTL lapsed before execution "
        "(dead-lettered with reason expired, EXPIRED rejection to the "
        "caller — never silently dropped)")

# -- batched silo→silo fabric (runtime/rpc.py RpcFabric) ---------------------
declare("rpc.fabric_frames_sent", KIND_COUNTER, "frames",
        "coalesced silo→silo frames shipped (one transport send per "
        "per-destination egress-ring flush)")
declare("rpc.fabric_frames_received", KIND_COUNTER, "frames",
        "coalesced silo→silo frames decoded on ingress")
declare("rpc.fabric_frames_rejected", KIND_COUNTER, "frames",
        "inbound fabric frames that failed to decode (dropped whole; "
        "senders recover via the per-message resend machinery)")
declare("rpc.fabric_calls_sent", KIND_COUNTER, "calls",
        "request/one-way members shipped inside fabric frames")
declare("rpc.fabric_calls_received", KIND_COUNTER, "calls",
        "request/one-way members ingested from fabric frames (TTL "
        "rebased per call on this silo's clock)")
declare("rpc.fabric_results_sent", KIND_COUNTER, "results",
        "response members shipped inside fabric frames")
declare("rpc.fabric_results_received", KIND_COUNTER, "results",
        "response members ingested from fabric frames and correlated "
        "through the callback table")
declare("rpc.fabric_fallbacks", KIND_COUNTER, "messages",
        "remote application messages ineligible for frame coalescing "
        "(rich context, ring full, encode failure) sent per-message — "
        "the counted correctness fallback, never silent")
declare("rpc.fabric_bounced", KIND_COUNTER, "messages",
        "frame members failed individually after a carrier bounce "
        "(dead peer / closed link): requests re-enter the resend "
        "machinery as TRANSIENT rejections, one-ways/responses "
        "dead-letter as undeliverable — no stranded callers")
declare("rpc.fabric_vector_batches", KIND_COUNTER, "batches",
        "forwarded call sections whose keys are vector-arena grains "
        "injected as ONE batched engine send instead of per-call turns")
declare("rpc.fabric_egress_batch", KIND_GAUGE, "messages",
        "mean members per shipped fabric frame over the last collection "
        "interval (1.0 = the fabric is degenerating to per-message)")

# -- per-message forwarding (runtime/dispatcher.py try_forward) --------------
declare("dispatch.forwarded", KIND_COUNTER, "messages",
        "messages re-routed after a stale/moved target "
        "(Dispatcher.try_forward; each hop increments forward_count "
        "until max_forward_count rejects UNRECOVERABLE)")
declare("dispatch.forward_depth", KIND_GAUGE, "hops",
        "deepest forward chain observed in the last collection "
        "interval (sustained values near max_forward_count mean the "
        "directory is chasing migrations)")

# -- tracing + cluster timeline plane (spans.py) -----------------------------
declare("trace.spans_started", KIND_COUNTER, "spans",
        "hop/tick/plane spans opened by the span recorder")
declare("trace.spans_committed", KIND_COUNTER, "spans",
        "spans committed to the sinks (flight ring + timeline + "
        "telemetry); unsampled-OK spans vanish before this counter")
declare("trace.sampled_traces", KIND_COUNTER, "traces",
        "head-sampling YES decisions minted at ingress (client, "
        "gateway, or fastpath trace mint)")
declare("trace.drop_spans", KIND_COUNTER, "spans",
        "always-on dead-letter drop spans (recorded regardless of "
        "sampling — failures never vanish)")
declare("trace.timeline_backlog", KIND_GAUGE, "events",
        "events currently retained in the per-silo timeline ring "
        "(spans + lifecycle + metric deltas awaiting collection)")
declare("trace.timeline_dropped", KIND_COUNTER, "events",
        "timeline events evicted by the ring bound before collection "
        "(non-zero = raise tracing.timeline_capacity or collect "
        "more often)")
declare("trace.worst_clock_offset_s", KIND_GAUGE, "seconds",
        "largest absolute peer clock-offset estimate from the "
        "probe-piggybacked handshake; -1 = no peer probed yet (the "
        "no-data sentinel — an empty estimate table must never read "
        "as perfectly synced)")

# -- device-resident cross-shard routing (tensor/exchange.py) ----------------
declare("route.cross_shard_msgs", KIND_COUNTER, "messages",
        "messages exchanged to a DIFFERENT mesh shard on device "
        "(all_to_all lanes; the traffic the host slab path no longer "
        "carries).  Exact when the structured exchange is engaged; a "
        "disengaged (identity-mode) silo reports a probe-sampled "
        "estimate scaled to totals")
declare("route.delivered_msgs", KIND_COUNTER, "messages",
        "messages delivered through the cross-shard exchange "
        "(local + cross-shard lanes, bucket overflows excluded)")
declare("route.exchange_dropped", KIND_COUNTER, "messages",
        "lanes that overflowed their destination bucket and were "
        "re-delivered next tick with their original inject stamp")
declare("route.exchanges", KIND_COUNTER, "dispatches",
        "cross-shard exchange dispatches (one per exchanged batch)")
declare("route.exchange_s", KIND_COUNTER, "seconds",
        "cumulative host wall time in the exchange stage (dispatch "
        "side; the device cost shows as the 'exchange' tick phase)")
declare("route.exchange_util", KIND_GAUGE, "ratio",
        "bucket utilization: live input lanes over the padded "
        "post-exchange lanes every downstream kernel pays for — "
        "occupancy-sized caps hold this near 1 (worst-case caps ran "
        "it at ~0.12)")
declare("route.exchange_overlap_s", KIND_COUNTER, "seconds",
        "overlap credit: wall time pre-dispatched exchanges had to "
        "run under the preceding groups' compute before their "
        "consuming group collected them")
declare("route.exchange_cap", KIND_GAUGE, "lanes",
        "occupancy-sized bucket cap toward one destination shard "
        "(label 'shard'): the ladder rung the measured peak demand "
        "quantizes to with headroom, maxed over sites — 0 means no "
        "cross-shard demand observed")
declare("route.exchange_cap_util", KIND_GAUGE, "ratio",
        "steady-state fill of the per-destination grant toward one "
        "shard (label 'shard'): last observed demand over the granted "
        "cap, maxed over sites — the proof the per-destination ladder "
        "sizes each lane to ITS traffic, not to the hottest pair's")
declare("arena.shard_occupancy", KIND_GAUGE, "rows",
        "live rows in one mesh shard block (labels 'arena', 'shard') — "
        "the per-shard balance behind the multichip bench")

# -- device fan-out (tensor/fanout.py) ---------------------------------------
declare("fanout.width", KIND_GAUGE, "lanes",
        "expansion width of a registered DeviceFanout's latest round "
        "(label 'route' = SrcType.method)")
declare("fanout.sized_rounds", KIND_COUNTER, "rounds",
        "expansion rounds sized from their host keys to the ladder rung "
        "at or above their exact degree sum (label 'route')")
declare("fanout.full_width_rounds", KIND_COUNTER, "rounds",
        "expansion rounds at the full CSR width: device-key sources and "
        "overflow redeliveries (label 'route')")
declare("fanout.lanes_needed", KIND_COUNTER, "lanes",
        "exact degree sums of the sized rounds (label 'route')")
declare("fanout.lanes_expanded", KIND_COUNTER, "lanes",
        "lanes the sized rounds expanded; 1 - lanes_needed / "
        "lanes_expanded is their padding share (label 'route')")
declare("fanout.dropped_lanes", KIND_COUNTER, "events",
        "publish source lanes parked by expansion-width overflow and "
        "re-expanded at the next quiescence point (label 'route')")
declare("fanout.redeliveries", KIND_COUNTER, "rounds",
        "overflow redelivery rounds run for parked publish lanes "
        "(label 'route')")

# -- device streams plane (tensor/streams_plane.py) --------------------------
declare("stream.published_events", KIND_COUNTER, "events",
        "stream-ingress publishes routed through a device subscription "
        "adjacency (label 'route' = SrcType.method)")
declare("stream.delivered_events", KIND_COUNTER, "events",
        "subscriber deliveries with host-known counts: pull-path edges "
        "+ host-fallback expansions (label 'route').  Push-path "
        "delivery volume is device-resident — count it per method via "
        "engine.latency_ticks / the attribution plane; "
        "stream.redeliveries tracks its overflow rounds")
declare("stream.subscriptions", KIND_GAUGE, "edges",
        "live (stream, subscriber) edges in the adjacency (label "
        "'route')")
declare("stream.cold_subscribers", KIND_GAUGE, "edges",
        "bound-pattern edges whose subscriber is not currently "
        "activated — the plane falls back to push delivery (which "
        "reactivates them) until the next rebuild (label 'route')")
declare("stream.rebuilds", KIND_COUNTER, "rebuilds",
        "device CSR re-lays (batched churn merges, eviction "
        "retirement, row moves; label 'route')")
declare("stream.retired_edges", KIND_COUNTER, "edges",
        "adjacency edges retired because their subscriber row was "
        "evicted BEFORE the slot could be reused (label 'route')")
declare("stream.dropped_lanes", KIND_COUNTER, "events",
        "publish source lanes parked by CSR-width overflow and "
        "re-expanded at the next quiescence point with their original "
        "inject stamp (label 'route'; never silent loss)")
declare("stream.redeliveries", KIND_COUNTER, "rounds",
        "overflow redelivery rounds run for parked publish lanes "
        "(label 'route')")

# -- device timers plane (tensor/timers_plane.py) ----------------------------
declare("timer.armed", KIND_GAUGE, "timers",
        "timers currently armed in the device timing wheel across all "
        "vector types (one-shots + periodics awaiting their next due "
        "tick)")
declare("timer.fired", KIND_COUNTER, "timers",
        "due timers harvested and injected as batched receive_reminder "
        "calls (a periodic counts once per firing)")
declare("timer.re_armed", KIND_COUNTER, "timers",
        "periodic timers re-armed in the same harvest kernel that "
        "fired them (phase-preserving: due += k*period)")
declare("timer.cancelled", KIND_COUNTER, "timers",
        "timers disarmed before firing (grain cancel or reminder "
        "unregister)")
declare("timer.exported", KIND_COUNTER, "timers",
        "armed timers shipped out with live grain migration (they "
        "re-arm on the target's wheel, relative dues preserved)")
declare("timer.adopted", KIND_COUNTER, "timers",
        "armed timers adopted from a migrating source silo")
declare("timer.mean_harvest_width", KIND_GAUGE, "timers",
        "mean fired timers per harvest since start — the batching win "
        "over one-task-per-reminder host scheduling")
declare("timer.worst_lateness_ticks", KIND_GAUGE, "ticks",
        "worst observed fire lateness in engine ticks (0 = every "
        "harvest caught its due bucket on the exact tick)")
declare("timer.harvest_seconds", KIND_COUNTER, "seconds",
        "host+device time spent in per-tick wheel advance/harvest — "
        "the overhead the timers bench A/Bs against a plane-off run")

# -- durable state plane (tensor/checkpoint.py) ------------------------------
declare("ckpt.full_snapshots", KIND_COUNTER, "snapshots",
        "full-arena columnar snapshots committed durable (consistent "
        "cuts pinned at a tick boundary, drained between ticks)")
declare("ckpt.delta_snapshots", KIND_COUNTER, "snapshots",
        "attribution-driven incremental deltas committed durable "
        "(only rows whose traffic counts moved since the last cut)")
declare("ckpt.rows_written", KIND_COUNTER, "rows",
        "arena rows written into committed snapshots (full + delta)")
declare("ckpt.bytes_written", KIND_COUNTER, "bytes",
        "snapshot blob bytes written to the snapshot store")
declare("ckpt.restored_rows", KIND_COUNTER, "rows",
        "arena rows restored by crash recovery")
declare("ckpt.age_ticks", KIND_GAUGE, "ticks",
        "ticks since the last COMMITTED recovery point — the live "
        "loss-window bound a hard kill would pay (-1 = no recovery "
        "point yet)")
declare("ckpt.pause_p99_s", KIND_GAUGE, "seconds",
        "p99 over recent checkpoint-plane per-tick pauses (pin + "
        "budgeted drain slices + journal seals)")
declare("ckpt.max_pause_s", KIND_GAUGE, "seconds",
        "worst checkpoint-plane per-tick pause since engine start")
declare("ckpt.dirty_rows", KIND_GAUGE, "rows",
        "rows the last incremental delta selected (attribution-counts "
        "moved | use clock advanced | key changed since the pin)")
declare("ckpt.restore_s", KIND_GAUGE, "seconds",
        "wall seconds of the last crash recovery (snapshot restore + "
        "journal fold-replay + re-anchor) — the recovery-time gauge "
        "the RTO bound judges")
declare("journal.appended_lanes", KIND_COUNTER, "lanes",
        "message lanes appended to device journal rings at ingress "
        "(write-ahead; durability lands at segment seal)")
declare("journal.segments", KIND_COUNTER, "segments",
        "journal segments sealed durable (blob + manifest committed) "
        "— the acknowledgement events of the durability contract")
declare("journal.ring_overflows", KIND_COUNTER, "flushes",
        "journal appends that crossed the buffered-lane bound and "
        "forced a mid-tick segment seal (size journal_ring_lanes to "
        "keep this 0 in steady state)")
declare("journal.replayed_lanes", KIND_COUNTER, "lanes",
        "journal lanes fold-replayed by crash recovery (one engine "
        "tick per journaled tick, never per-event)")
declare("journal.flush_s", KIND_COUNTER, "seconds",
        "cumulative host wall time sealing journal segments (the d2h "
        "ring drain + blob write + manifest commit)")
declare("journal.pending_lanes", KIND_GAUGE, "lanes",
        "lanes in open journal rings NOT yet sealed durable — the "
        "journal half of the loss window a hard kill would pay")
declare("ckpt.standby_lag_ticks", KIND_GAUGE, "ticks",
        "ticks this warm standby trails the primary's durable horizon "
        "(committed recovery point + sealed journal segments); -1 = "
        "this silo is not a standby — the sentinel dominates the "
        "cluster row so a cluster with no failover cover shows -1")
declare("ckpt.standby_polls", KIND_COUNTER, "polls",
        "standby tailing steps against the primary's snapshot store "
        "(log shipping over the durable plane, no new wire protocol)")
declare("ckpt.standby_adopted_rows", KIND_COUNTER, "rows",
        "arena rows a warm standby adopted from the primary's "
        "committed fulls/deltas ahead of any promotion")
declare("ckpt.standby_staged_segments", KIND_GAUGE, "segments",
        "sealed journal segments staged host-side on the standby, "
        "ready to fold-replay at promotion (never applied early — "
        "deltas record absolute values)")
declare("recovery.promotions", KIND_COUNTER, "promotions",
        "standby promotions this engine performed (fence acquired + "
        "staged tail replayed + range taken over)")
declare("recovery.last_rto_s", KIND_GAUGE, "seconds",
        "wall seconds of the last standby promotion — the measured "
        "failover RTO (fence + final catch-up + tail fold-replay)")
declare("recovery.fused_windows", KIND_COUNTER, "windows",
        "journal fold-replay windows executed as ONE fused program "
        "over consecutive journaled ticks (autofuse machinery) "
        "instead of per-tick engine calls")
declare("recovery.fused_lanes", KIND_COUNTER, "lanes",
        "journal lanes replayed through fused windows (subset of "
        "journal.replayed_lanes)")

# -- transport links (runtime/transport per-link stats) ----------------------
for _n, _u, _d in (
        ("frames_sent", "frames", "wire frames sent on this link"),
        ("bytes_sent", "bytes", "payload bytes sent on this link"),
        ("slab_frames_sent", "frames", "zero-copy slab frames on this link"),
        ("drain_cycles", "cycles", "sender batching drain cycles"),
        ("msgs_bounced", "messages", "messages bounced by queue byte caps")):
    declare(f"transport.link.{_n}", KIND_COUNTER, _u, _d)

# -- engine / device latency ledger (tensor/engine + tensor/ledger) ----------
declare("engine.messages_processed", KIND_COUNTER, "messages",
        "messages applied by the tensor engine")
declare("engine.ticks", KIND_COUNTER, "ticks", "engine ticks executed")
declare("engine.compiles", KIND_COUNTER, "programs",
        "step-program compilations (shape churn indicator)")
declare("engine.tick_seconds", KIND_COUNTER, "seconds",
        "cumulative host wall time inside run_tick")
declare("engine.latency_ticks", KIND_HISTOGRAM, "ticks",
        "per-message turn latency in device ticks (the on-device "
        "latency ledger: inject-tick to completion-tick delta; "
        "label 'method' = Type.method)")
# -- continuous pipelined ticking (tensor/engine.TickPipeline) ---------------
declare("engine.inflight_ticks", KIND_GAUGE, "ticks",
        "ticks dispatched but not yet completion-signalled (the "
        "pipelined loop's in-flight window; bounded by pipeline_depth)")
declare("engine.overlap_s", KIND_COUNTER, "seconds",
        "device execution time that ran concurrently with later host "
        "work (completion-event timestamp minus dispatch-return "
        "timestamp; the profiler's phase-reconciliation credit)")
declare("engine.donation_fallbacks", KIND_COUNTER, "programs",
        "step/fused executions on the undonated fallback path "
        "(donate_state off or an explicitly pinned program) — state "
        "stops double-buffering in place when this moves")
declare("engine.latency_budget_s", KIND_GAUGE, "seconds",
        "the live target_tick_latency budget (0 = unbounded); the "
        "dashboard judges the device-ledger p99 against it")
declare("tensor.shards", KIND_GAUGE, "shards",
        "mesh shards the silo's engine spans (tensor.mesh_devices): 1 "
        "runs on one device with no exchange; above 1 the arenas are "
        "sharded and cross-shard messages take the device exchange")

# -- device cost plane (tensor/profiler.py + tensor/memledger.py) ------------
declare("engine.phase_s", KIND_HISTOGRAM, "seconds",
        "per-tick wall time of one pipeline phase (label 'phase' = "
        "host | h2d | exchange | dispatch | route | d2h; the tick-phase "
        "profiler's log2 histograms mirrored per phase)")
declare("compile.events", KIND_COUNTER, "compiles",
        "cause-coded compile/retrace events (label 'cause' = the "
        "tensor/profiler.py churn cause list: new_method, bucket_growth, "
        "shape_change, epoch_mismatch, generation_repack, config_toggle, "
        "mesh_reshard, new_window, cross_shard)")
declare("compile.lowering_s", KIND_COUNTER, "seconds",
        "cumulative lowering/compile wall time across tracked retraces")
declare("memory.self_bytes", KIND_GAUGE, "bytes",
        "HBM accounted by the device memory ledger (arena columns, "
        "mirrors, clocks, pending slabs, latency-ledger hist)")
declare("memory.peak_bytes", KIND_GAUGE, "bytes",
        "peak self-accounted HBM observed since engine start")
declare("memory.owner_bytes", KIND_GAUGE, "bytes",
        "self-accounted HBM of one owner group (label 'owner' = "
        "arena.<type> | pending_batches | latency_ledger | "
        "autofuse_chain)")
declare("memory.device_bytes_in_use", KIND_GAUGE, "bytes",
        "backend-reported bytes in use (device.memory_stats; absent on "
        "backends without the query)")
declare("memory.device_bytes_limit", KIND_GAUGE, "bytes",
        "backend-reported HBM capacity (device.memory_stats)")
declare("memory.headroom", KIND_GAUGE, "ratio",
        "free HBM fraction (1 - in_use/limit); the ShedController "
        "floors its shed level below the configured low watermark")

# -- workload attribution plane (tensor/attribution.py) ----------------------
declare("hot.tracked_msgs", KIND_COUNTER, "messages",
        "message lanes folded into the attribution plane (per-row "
        "traffic counts + count-min sketch; live + retired)")
declare("hot.method_msgs", KIND_COUNTER, "messages",
        "messages applied per (type, method) slot (label 'method' = "
        "Type.method; the attribution plane's traffic-share numerator)")
declare("hot.grain_msgs", KIND_GAUGE, "messages",
        "messages received by one HotSet grain since engine start "
        "(labels 'arena', 'key'; the candidate top-K read off the "
        "device counts column, merged with eviction-retired history)")
declare("hot.grain_share", KIND_GAUGE, "ratio",
        "one HotSet grain's share of its arena's tracked traffic "
        "(labels 'arena', 'key') — the hot-shard detection signal")
declare("hot.topk_share", KIND_GAUGE, "ratio",
        "combined traffic share of the arena's top-K grains (label "
        "'arena'; 1.0 = all traffic lands on K grains)")
declare("hot.confidence", KIND_GAUGE, "ratio",
        "count-min sketch confidence of the HotSet estimates "
        "(1 - exp(-depth); the error bound is (e/width) * total)")
declare("skew.max_shard_share", KIND_GAUGE, "ratio",
        "largest mesh-shard's share of one arena's traffic (label "
        "'arena'; 1/n_shards = perfectly balanced)")
declare("skew.gini", KIND_GAUGE, "ratio",
        "Gini coefficient of per-grain traffic over one arena's live "
        "rows (label 'arena'; 0 = uniform, →1 = one grain takes all)")
declare("skew.p99_to_mean", KIND_GAUGE, "ratio",
        "p99 per-grain message count over the mean across live rows "
        "(label 'arena'; the heavy-tail gauge)")

# -- cluster SLO rollup (silo.collect_metrics; dashboard slo row) ------------
declare("slo.latency_window_msgs", KIND_COUNTER, "messages",
        "messages judged against the latency budget (device-ledger "
        "totals while a target_tick_latency budget is set)")
declare("slo.latency_over_budget", KIND_COUNTER, "messages",
        "messages whose device-ledger latency bucket lies entirely "
        "above the budget (conservative: only surely-over buckets)")
declare("slo.latency_burn_rate", KIND_GAUGE, "ratio",
        "latency SLO burn: over-budget fraction / error budget "
        "(> 1 = the silo is burning its latency budget)")
declare("slo.latency_error_budget", KIND_GAUGE, "ratio",
        "configured latency error budget (MetricsConfig."
        "slo_latency_error_budget)")
declare("slo.dropped_msgs", KIND_COUNTER, "messages",
        "terminally dropped or shed messages counted against the drop "
        "SLO (dead letters + adaptive shed)")
declare("slo.attempted_msgs", KIND_COUNTER, "messages",
        "messages offered to the silo (engine + host path + drops; the "
        "drop SLO's denominator)")
declare("slo.drop_burn_rate", KIND_GAUGE, "ratio",
        "drop SLO burn: dropped fraction / error budget")
declare("slo.drop_error_budget", KIND_GAUGE, "ratio",
        "configured drop error budget (MetricsConfig."
        "slo_drop_error_budget)")
declare("slo.healthy", KIND_GAUGE, "bool",
        "1 when every burn rate is within budget on this silo, else 0 "
        "— the dashboard's one-look cluster-health answer")

# -- closed-loop rebalance (runtime/rebalancer.py; dashboard row) ------------
declare("rebalance.intervals", KIND_COUNTER, "intervals",
        "controller decision intervals run (signals read + judged)")
declare("rebalance.moves", KIND_COUNTER, "waves",
        "shard-leg move waves applied (one batched migrate_keys per "
        "wave)")
declare("rebalance.grains_moved", KIND_COUNTER, "grains",
        "grains the controller migrated between device-shard blocks")
declare("rebalance.cross_silo_grains", KIND_COUNTER, "grains",
        "grains the controller migrated to a peer silo (placement "
        "override + state-slab push)")
declare("rebalance.skipped", KIND_COUNTER, "intervals",
        "intervals the controller judged and chose NOT to act (label "
        "'reason': idle / below_trigger / hysteresis / cooldown / "
        "no_candidates — the convergence-not-thrash counters)")
declare("rebalance.trigger_share", KIND_GAUGE, "ratio",
        "the burning shard's interval traffic share at the last applied "
        "move (what the controller acted on)")
declare("rebalance.move_pause_s", KIND_GAUGE, "seconds",
        "worst single migration wave pause so far (the bounded-pause "
        "contract the chaos storm asserts)")
declare("rebalance.migrations", KIND_COUNTER, "waves",
        "batched live-migration operations on this engine from ANY "
        "source (controller, ring-change handoff, drain)")
declare("rebalance.migrated_grains", KIND_COUNTER, "grains",
        "grains live-migrated on this engine from any source")
declare("rebalance.replicated", KIND_COUNTER, "grains",
        "hot grains promoted to replica rows across shards (the "
        "controller's second actuator — for grains too hot for ANY "
        "single shard, where migration just relocates the burn)")
declare("rebalance.demoted", KIND_COUNTER, "grains",
        "replicated grains folded back to one row after their traffic "
        "cooled (demote_share for demote_patience intervals)")
declare("rebalance.replica_folds", KIND_COUNTER, "folds",
        "commutative replica-state folds performed (demotion, "
        "checkpoint and read paths — each is one segment reduction)")
declare("rebalance.hot_grain_blocked", KIND_COUNTER, "intervals",
        "burning-shard intervals whose heat rode one grain below the "
        "mover floor — previously a silent forever-armed idle, now "
        "routed to the replication decision")

# -- host control path (stats.SiloMetrics mirror) ----------------------------
declare("host.requests_sent", KIND_COUNTER, "requests",
        "application requests sent on the host path")
declare("host.requests_resent", KIND_COUNTER, "requests",
        "transient resends on the host path")
declare("host.turns_executed", KIND_COUNTER, "turns",
        "activation turns executed")
declare("host.turn_latency_s", KIND_HISTOGRAM, "seconds",
        "host-path activation turn latency")


# ---------------------------------------------------------------------------
# log2 histogram (shared bucket math with the device ledger)
# ---------------------------------------------------------------------------

def bucket_index(value: float, base: float, n_buckets: int) -> int:
    """The canonical log2 bucket of ``value``: bucket 0 holds values
    < ``base``; bucket k (k >= 1) holds [base * 2**(k-1), base * 2**k);
    the last bucket absorbs overflow.  The device ledger's tick deltas
    use the same scheme with base=1 (bucket 0 = completed in the inject
    tick, bucket 1 = 1 tick, bucket 2 = 2-3 ticks, ...)."""
    if value < base:
        return 0
    return min(int(np.floor(np.log2(value / base))) + 1, n_buckets - 1)


def bucket_bounds(base: float, n_buckets: int) -> List[Tuple[float, float]]:
    """[(lo, hi)) value range of every bucket (hi of the overflow bucket
    is inf)."""
    out = [(0.0, base)]
    for k in range(1, n_buckets):
        hi = base * (2.0 ** k) if k < n_buckets - 1 else float("inf")
        out.append((base * (2.0 ** (k - 1)), hi))
    return out


def percentile_from_counts(counts: Sequence[int], p: float,
                           base: float = 1.0) -> float:
    """Estimate the p-th percentile (p in [0, 100]) from log2 bucket
    counts: find the bucket holding the target rank and interpolate
    linearly inside it.  The estimate always lies inside its bucket, so
    the relative error vs the exact value is bounded by the bucket's
    octave width (<= 2x; tests/test_metrics.py asserts it)."""
    counts = np.asarray(counts, dtype=np.int64)
    total = int(counts.sum())
    if total == 0:
        return 0.0
    target = max(1.0, (p / 100.0) * total)
    bounds = bucket_bounds(base, len(counts))
    seen = 0
    for k, n in enumerate(counts):
        if n == 0:
            continue
        if seen + n >= target:
            lo, hi = bounds[k]
            if not np.isfinite(hi):
                hi = lo * 2.0  # overflow bucket: report its lower octave
            frac = (target - seen) / n
            return float(lo + frac * (hi - lo))
        seen += int(n)
    lo, hi = bounds[-1]
    return float(lo)


class Log2Histogram:
    """Fixed log2-bucket histogram (host instrument; the device ledger
    accumulates the identical bucket layout on the mesh)."""

    __slots__ = ("base", "counts", "total", "sum")

    def __init__(self, n_buckets: int = 32, base: float = 1.0) -> None:
        self.base = base
        self.counts = np.zeros(n_buckets, dtype=np.int64)
        self.total = 0
        self.sum = 0.0

    def observe(self, value: float, count: int = 1) -> None:
        self.counts[bucket_index(value, self.base, len(self.counts))] += count
        self.total += count
        self.sum += value * count

    def add_counts(self, counts: Sequence[int],
                   value_sum: float = 0.0) -> None:
        """Merge an externally-accumulated bucket array (the device
        ledger's d2h transfer lands here).  Bucket layouts must match."""
        counts = np.asarray(counts, dtype=np.int64)
        if len(counts) != len(self.counts):
            raise ValueError(
                f"bucket count mismatch: {len(counts)} vs {len(self.counts)}")
        self.counts += counts
        self.total += int(counts.sum())
        self.sum += value_sum

    def set_counts(self, counts: Sequence[int],
                   value_sum: float = 0.0) -> None:
        """MIRROR an externally-accumulated cumulative bucket array (the
        device latency ledger, the host turn-latency histogram): replaces
        the counts rather than adding, so periodic re-publication of a
        cumulative source never double-counts."""
        counts = np.asarray(counts, dtype=np.int64)
        if len(counts) != len(self.counts):
            raise ValueError(
                f"bucket count mismatch: {len(counts)} vs {len(self.counts)}")
        self.counts = counts.copy()
        self.total = int(counts.sum())
        self.sum = value_sum

    def merge(self, other: "Log2Histogram") -> None:
        if other.base != self.base:
            raise ValueError("cannot merge histograms with different bases")
        self.add_counts(other.counts, other.sum)

    def percentile(self, p: float) -> float:
        return percentile_from_counts(self.counts, p, self.base)

    @property
    def mean(self) -> float:
        return self.sum / self.total if self.total else 0.0

    def to_dict(self) -> Dict[str, Any]:
        return {"base": self.base, "counts": self.counts.tolist(),
                "total": self.total, "sum": round(self.sum, 9)}


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------

class Counter:
    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        self.value += n

    def set_total(self, total: float) -> None:
        """Mirror an externally-accumulated cumulative total (the silo's
        periodic collection mirrors component counters that already count
        for themselves — monotonicity is kept so a stale publish can
        never rewind the registry)."""
        if total > self.value:
            self.value = total


class Gauge:
    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = v


def _label_key(labels: Optional[Dict[str, Any]]) -> str:
    if not labels:
        return ""
    return ",".join(f"{k}={labels[k]}" for k in sorted(labels))


class MetricsRegistry:
    """Per-silo (or per-process) typed metric store.

    Every instrument is keyed by (catalogued name, label set).  Unknown
    names raise — the catalog is the contract that keeps dashboards from
    meeting undeclared strings.  Updates are plain attribute arithmetic
    on the owning event loop (lock-cheap: no locks, no allocation on the
    increment path once the instrument exists)."""

    def __init__(self, source: str = "",
                 histogram_buckets: int = 32) -> None:
        self.source = source
        self.histogram_buckets = histogram_buckets
        self._counters: Dict[Tuple[str, str], Counter] = {}
        self._gauges: Dict[Tuple[str, str], Gauge] = {}
        self._histograms: Dict[Tuple[str, str], Log2Histogram] = {}

    # -- instrument access ---------------------------------------------------

    def _check(self, name: str, kind: str) -> MetricSpec:
        spec = CATALOG.get(name)
        if spec is None:
            raise KeyError(
                f"metric {name!r} is not declared in the metrics catalog "
                "(orleans_tpu/metrics.py CATALOG) — declare name, kind, "
                "unit and doc before emitting it")
        if spec.kind != kind:
            raise TypeError(f"metric {name!r} is a {spec.kind}, not {kind}")
        return spec

    def counter(self, name: str,
                labels: Optional[Dict[str, Any]] = None) -> Counter:
        self._check(name, KIND_COUNTER)
        key = (name, _label_key(labels))
        inst = self._counters.get(key)
        if inst is None:
            inst = self._counters[key] = Counter()
        return inst

    def gauge(self, name: str,
              labels: Optional[Dict[str, Any]] = None) -> Gauge:
        self._check(name, KIND_GAUGE)
        key = (name, _label_key(labels))
        inst = self._gauges.get(key)
        if inst is None:
            inst = self._gauges[key] = Gauge()
        return inst

    def drop_gauges(self, name: str) -> None:
        """Remove every labeled instance of one gauge family — for
        re-published bounded sets (the HotSet's (arena, key) rows)
        whose label VALUES churn: without the drop, a grain that left
        the hot set would keep its last cumulative gauge in every later
        snapshot forever, and the label cardinality would grow without
        bound over a long-running silo's life."""
        self._check(name, KIND_GAUGE)
        for key in [k for k in self._gauges if k[0] == name]:
            del self._gauges[key]

    def histogram(self, name: str, labels: Optional[Dict[str, Any]] = None,
                  base: float = 1.0,
                  n_buckets: Optional[int] = None) -> Log2Histogram:
        self._check(name, KIND_HISTOGRAM)
        key = (name, _label_key(labels))
        inst = self._histograms.get(key)
        if inst is None:
            inst = self._histograms[key] = Log2Histogram(
                n_buckets or self.histogram_buckets, base)
        elif n_buckets is not None and len(inst.counts) != n_buckets:
            # the source's bucket layout changed (a live ledger_buckets
            # reload resets the device ledger too): recreate rather than
            # raise — a layout change must never kill a publish loop
            inst = self._histograms[key] = Log2Histogram(n_buckets, base)
        return inst

    def apply(self, name: str, value: float,
              labels: Optional[Dict[str, Any]] = None,
              cumulative: bool = True) -> None:
        """Route one (name, value) observation by the catalog's kind —
        the migration shim for the ad-hoc ``track_metric`` call sites:
        counters mirror cumulative totals (``cumulative=False``
        increments instead), gauges set, histograms observe."""
        spec = CATALOG.get(name)
        if spec is None:
            raise KeyError(f"metric {name!r} is not declared in the "
                           "metrics catalog")
        if spec.kind == KIND_COUNTER:
            c = self.counter(name, labels)
            c.set_total(value) if cumulative else c.inc(value)
        elif spec.kind == KIND_GAUGE:
            self.gauge(name, labels).set(value)
        else:
            # seconds-valued histograms get a microsecond base so the
            # octave resolution covers real latency ranges
            base = 1e-6 if spec.unit == "seconds" else 1.0
            self.histogram(name, labels, base=base).observe(value)

    # -- snapshots -----------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """Plain-JSON state; ``merge_snapshots`` folds many of these into
        a cluster view."""
        counters: Dict[str, Dict[str, float]] = {}
        for (name, lk), c in self._counters.items():
            counters.setdefault(name, {})[lk] = c.value
        gauges: Dict[str, Dict[str, Dict[str, float]]] = {}
        src = self.source or "local"
        for (name, lk), g in self._gauges.items():
            gauges.setdefault(name, {})[lk] = {src: g.value}
        histograms: Dict[str, Dict[str, Any]] = {}
        for (name, lk), h in self._histograms.items():
            histograms.setdefault(name, {})[lk] = h.to_dict()
        return {"source": self.source, "counters": counters,
                "gauges": gauges, "histograms": histograms}


def merge_snapshots(snapshots: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold per-silo registry snapshots into one cluster view.  Counters
    and histogram buckets ADD (associative + commutative — aggregation
    order cannot change the result; tests assert it); gauges keep their
    per-source values (a shed level is not additive across silos)."""
    counters: Dict[str, Dict[str, float]] = {}
    gauges: Dict[str, Dict[str, Dict[str, float]]] = {}
    histograms: Dict[str, Dict[str, Dict[str, Any]]] = {}
    sources: List[str] = []
    for snap in snapshots:
        if not snap:
            continue
        sources.append(snap.get("source", ""))
        for name, by_label in snap.get("counters", {}).items():
            dst = counters.setdefault(name, {})
            for lk, v in by_label.items():
                dst[lk] = dst.get(lk, 0.0) + v
        for name, by_label in snap.get("gauges", {}).items():
            dst = gauges.setdefault(name, {})
            for lk, by_src in by_label.items():
                dst.setdefault(lk, {}).update(by_src)
        for name, by_label in snap.get("histograms", {}).items():
            dst = histograms.setdefault(name, {})
            for lk, h in by_label.items():
                cur = dst.get(lk)
                if cur is None:
                    dst[lk] = {"base": h["base"],
                               "counts": list(h["counts"]),
                               "total": h["total"], "sum": h["sum"]}
                else:
                    if cur["base"] != h["base"] \
                            or len(cur["counts"]) != len(h["counts"]):
                        raise ValueError(
                            f"histogram {name!r} bucket layouts differ "
                            "across snapshots")
                    cur["counts"] = [a + b for a, b
                                     in zip(cur["counts"], h["counts"])]
                    cur["total"] += h["total"]
                    cur["sum"] += h["sum"]
    return {"source": "+".join(s for s in sources if s),
            "counters": counters, "gauges": gauges,
            "histograms": histograms}


def histogram_percentiles(hist: Dict[str, Any],
                          ps: Sequence[float] = (50, 95, 99)
                          ) -> Dict[str, float]:
    """p50/p95/p99 (configurable) of one snapshot histogram entry."""
    return {f"p{int(p) if float(p).is_integer() else p}":
            percentile_from_counts(hist["counts"], p, hist["base"])
            for p in ps}


# ---------------------------------------------------------------------------
# catalog documentation (METRICS.md is generated from here — the test in
# tests/test_metrics.py fails when the checked-in file drifts)
# ---------------------------------------------------------------------------

def generate_doc() -> str:
    """Render the CATALOG as the METRICS.md markdown: one table per
    dotted-prefix group, deterministic order, nothing hand-written —
    ``python -m orleans_tpu.metrics --doc > METRICS.md`` regenerates."""
    lines = [
        "# Metrics catalog",
        "",
        "Every metric name the runtime may emit, generated from the one",
        "source of truth (`orleans_tpu/metrics.py` `CATALOG`).  Do not",
        "edit by hand — regenerate with:",
        "",
        "```bash",
        "python -m orleans_tpu.metrics --doc > METRICS.md",
        "```",
        "",
        "The registry refuses undeclared names and the catalog lint",
        "(`tests/test_metrics.py`) walks the source tree asserting every",
        "emitted literal is declared, so this file is complete by",
        "construction.",
    ]
    groups: Dict[str, List[MetricSpec]] = {}
    for name in sorted(CATALOG):
        groups.setdefault(name.split(".", 1)[0], []).append(CATALOG[name])
    for prefix in sorted(groups):
        lines += ["", f"## `{prefix}.*`", "",
                  "| name | kind | unit | description |",
                  "|---|---|---|---|"]
        for spec in groups[prefix]:
            doc = " ".join(spec.doc.split())
            lines.append(f"| `{spec.name}` | {spec.kind} | {spec.unit} "
                         f"| {doc} |")
    lines.append("")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        prog="python -m orleans_tpu.metrics",
        description="metrics catalog tooling")
    parser.add_argument("--doc", action="store_true",
                        help="print the generated METRICS.md content")
    args = parser.parse_args(argv)
    if args.doc:
        print(generate_doc(), end="")
        return 0
    parser.print_help()
    return 2


if __name__ == "__main__":
    import sys
    sys.exit(main())
