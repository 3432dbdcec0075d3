"""Cluster / silo / client configuration.

Parity: reference configuration system (reference: src/Orleans/Configuration/
ClusterConfiguration.cs, GlobalConfiguration.cs — liveness :149-194,
directory cache :247-275, placement defaults :353-357; NodeConfiguration.cs;
ClientConfiguration.cs; LimitManager.cs:34).  XML loading is replaced by
plain dataclasses + ``from_dict`` (programmatic construction was equally
supported in the reference and is what its test host used).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, Optional


@dataclass
class LivenessConfig:
    """(reference: GlobalConfiguration liveness section :149-194)"""

    probe_timeout: float = 0.5            # ProbeTimeout
    table_refresh_timeout: float = 5.0    # TableRefreshTimeout
    death_vote_expiration: float = 120.0  # DeathVoteExpirationTimeout
    iam_alive_table_publish: float = 5.0  # IAmAliveTablePublishTimeout
    num_missed_probes_limit: int = 3      # NumMissedProbesLimit
    num_probed_silos: int = 3             # NumProbedSilos
    num_votes_for_death: int = 2          # NumVotesForDeathDeclaration
    probe_period: float = 1.0
    # per-peer gossip RPC timeout (also bounds the shutdown goodbye wait);
    # hoisted from the hard-coded 1.0 so chaos plans/tests can tighten it
    gossip_timeout: float = 1.0
    # fast-suspect: a non-quorum suspect vote gossips the suspicion
    # immediately so other members probe the victim out-of-band and add
    # their votes now, instead of waiting for their own probe rounds to
    # notice — detection converges within ~probe_timeout of the first
    # vote rather than another probe_period * num_missed_probes_limit
    fast_suspect: bool = True


@dataclass
class DirectoryConfig:
    """(reference: GlobalConfiguration directory cache section :247-275)"""

    cache_size: int = 100_000
    buckets_per_silo: int = 30            # virtual-bucket ring


@dataclass
class CollectionConfig:
    collection_quantum: float = 60.0      # ActivationCollector quantum
    default_age_limit: float = 7200.0     # DefaultCollectionAgeLimit (2h)


@dataclass
class MessagingConfig:
    response_timeout: float = 30.0        # ResponseTimeout
    max_forward_count: int = 2            # MaxForwardCount
    max_resend_count: int = 3             # MaxResendCount
    deadlock_detection: bool = True       # PerformDeadlockDetection
    max_enqueued_requests: int = 5000     # LimitManager MaxEnqueuedRequests


@dataclass
class RpcConfig:
    """Batched host-RPC plane knobs (orleans_tpu/runtime/rpc.py).  No
    reference analog — the reference's Gateway/Dispatcher forward one
    Message at a time; this is the coalesced-window rebuild of that
    control path (the same batching move dispatch itself got)."""

    # hosted-client/gateway calls ride the coalescer + pre-resolved
    # invoke tables instead of the per-message pipeline.  Live-
    # reloadable (silo.update_config); OFF is the A/B baseline the rpc
    # bench tier measures against.  Sampled traces, chaos injection,
    # shed pressure and grain-to-grain calls always fall back to the
    # per-message path regardless of this flag.
    fastpath_enabled: bool = True
    # max calls per coalesced (type, method) window; a longer run
    # splits into consecutive windows (per-sender FIFO still holds)
    max_window: int = 8192
    # ingress-ring bound: submissions past this many pending calls are
    # refused back to the per-message path (its mailbox/shed machinery
    # is the real backpressure surface)
    max_pending: int = 131072

    # -- silo→silo fabric (runtime/rpc.py RpcFabric) --------------------
    # eligible remote application sends coalesce into per-destination
    # egress rings and ship as ONE sectioned rpc frame per flush; OFF is
    # the batched-vs-per-message A/B arm the rpc bench measures against.
    # Ineligible traffic (string/uuid keys, grain-to-grain call chains,
    # piggybacked invalidations) always stays per-message — counted as
    # rpc.fabric_fallbacks, never silent.  Live-reloadable.
    fabric_enabled: bool = True
    # a destination ring reaching this depth flushes inline instead of
    # waiting for the loop-idle drain (bulk-forwarding amortization cap)
    fabric_flush_lanes: int = 512
    # >0: the drain task holds small batches up to this long before
    # flushing (µs); 0 = flush at the next loop-idle point — single-call
    # p50 stays within the bench-gated bound of the per-message path
    fabric_flush_us: int = 0
    # per-destination ring bound: past this, sends fall back to the
    # per-message path (the transport's queue limits then apply)
    fabric_max_pending: int = 65536


@dataclass
class ResilienceConfig:
    """Overload containment & failure isolation knobs (orleans_tpu/
    resilience.py + limits.ShedController).  No single reference analog —
    the reference had binary LoadShedding and immediate transient resends;
    this is the SRE retry-budget / breaker / adaptive-shed discipline
    layered over the same call paths."""

    # transient-resend backoff (exponential, full jitter); disabling is
    # the no-backoff baseline for A/B measurement
    backoff_enabled: bool = True
    backoff_base: float = 0.02
    backoff_cap: float = 1.0
    # token-bucket retry budget per silo: first attempts deposit
    # retry_budget_fill tokens, each resend withdraws 1.0 — caps
    # cluster-wide retry amplification at ~fill rate in steady state
    retry_budget_capacity: float = 64.0
    retry_budget_fill: float = 0.1
    # per-destination circuit breakers (consulted before enqueue for
    # APPLICATION traffic; system/membership traffic always flows)
    breaker_enabled: bool = True
    breaker_failure_threshold: int = 5
    breaker_reset_timeout: float = 1.0
    breaker_half_open_probes: int = 1
    # adaptive admission control (limits.ShedController): shed level rises
    # linearly from queue_soft to queue_hard pending turns; at level L a
    # request sheds when its remaining TTL < L * shed_ttl_reference
    # (read-only requests at 2x the threshold — lower priority)
    shed_enabled: bool = True
    shed_queue_soft: int = 1000
    shed_queue_hard: int = 5000
    shed_ttl_reference: float = 30.0
    shed_sample_period: float = 0.02
    shed_stall_level: float = 0.5
    shed_stall_window: float = 2.0
    # bounded dead-letter ring (counters are exact and unbounded)
    dead_letter_capacity: int = 512


@dataclass
class TracingConfig:
    """Distributed-tracing plane knobs (orleans_tpu/spans.py).  No single
    reference analog — the reference's Message.AddTimestamp per-hop
    breadcrumbs generalized to Dapper-style causal spans with head
    sampling and a crash flight recorder."""

    enabled: bool = True
    # head-based sampling rate decided at client/gateway ingress; spans
    # ending in error/timeout/any dead-letter drop record ALWAYS
    sample_rate: float = 0.01
    # bounded per-silo ring of recent completed spans (the crash flight
    # recorder dumped on chaos invariant failure / degraded snapshot)
    flight_recorder_capacity: int = 256
    # recent circuit-breaker transitions retained for the dump
    breaker_transition_capacity: int = 64
    # cluster timeline plane (orleans_tpu/timeline.py): per-silo bounded
    # log of completed spans + lifecycle events + interval metric
    # deltas, merged onto a common clock and exported as TIMELINE.json
    # + a Perfetto (Chrome trace-event) file
    timeline_enabled: bool = True
    timeline_capacity: int = 4096


@dataclass
class MetricsConfig:
    """Unified metrics plane knobs (orleans_tpu/metrics.py registry +
    tensor/ledger.py device latency ledger).  No single reference analog
    — the reference's CounterStatistic groups generalized to a typed,
    catalogued, cluster-mergeable registry with an ON-DEVICE latency
    histogram.  Live-reloadable like TracingConfig (silo.update_config
    re-pushes ledger enable/bucket changes into the running engine)."""

    enabled: bool = True
    # on-device per-(type, method) latency ledger: messages are stamped
    # with their injection tick and tick-delta latencies accumulate into
    # log2-bucket histograms ON the device — only the small bucket-count
    # array ever crosses d2h (at the publish cadence), never per message
    ledger_enabled: bool = True
    # log2 buckets per (type, method) histogram: bucket 0 = completed in
    # the inject tick, bucket k = [2**(k-1), 2**k) ticks; 16 covers
    # deltas up to 16k ticks before the overflow bucket absorbs
    ledger_buckets: int = 16
    # ticks between device→host ledger fetches when the periodic
    # collection (load publisher / stats loop) asks for a snapshot; an
    # explicit ledger.snapshot() always fetches
    publish_interval_ticks: int = 32
    # workload attribution plane (tensor/attribution.py): per-row
    # traffic counts + count-min sketch + per-method slots accumulated
    # on device, HotSet/skew published by collect_metrics and the load
    # broadcast.  Live-reloadable; a toggle re-traces fused windows
    # (cause config_toggle), the ledger discipline.
    attribution_enabled: bool = True
    # hot grains published per snapshot (the candidate top-K read off
    # the device counts column; also the HotSet length)
    attribution_top_k: int = 16
    # count-min sketch layout: error bound est-true <= (e/width)*N with
    # probability >= 1 - exp(-depth); 4x8192 int32 = 128KB per arena
    attribution_cms_depth: int = 4
    attribution_cms_width: int = 8192
    # SLO rollup (slo.* catalog rows): the latency SLO is "all but this
    # fraction of messages complete within the engine's latency budget"
    # (engine.config.target_tick_latency; no budget = no latency SLO),
    # the drop SLO is "all but this fraction of offered messages are
    # delivered" (dead letters + shed vs attempted).  Burn rate =
    # observed error fraction / error budget; > 1 is unhealthy.
    slo_latency_error_budget: float = 0.01
    slo_drop_error_budget: float = 0.001


@dataclass
class ProfilerConfig:
    """Device cost plane knobs (orleans_tpu/tensor/profiler.py tick-phase
    profiler + compile-churn attribution, orleans_tpu/tensor/memledger.py
    HBM ledger).  No single reference analog — the reference's
    StageAnalysis (src/Orleans/Statistics/StageAnalysis.cs:81) generalized
    to an always-on, cheap cost-attribution plane in the spirit of
    Google-Wide Profiling.  Live-reloadable like TracingConfig
    (silo.update_config re-pushes into the running engine)."""

    enabled: bool = True
    # log2 buckets of the per-phase host histograms (base 1us; bucket 0
    # < 1us, bucket k = [2**(k-1), 2**k) us) — 24 covers ~4s phases
    phase_buckets: int = 24
    # triggered deep capture: when a tick's wall time breaches this
    # threshold the NEXT capture_ticks ticks are captured with
    # jax.profiler into capture_dir (trace referenced from the flight
    # recorder).  0 disables the trigger; silo.capture_profile(ticks=N)
    # captures explicitly regardless.
    capture_threshold_s: float = 0.0
    capture_ticks: int = 4
    # wall-clock backstop on a capture: the tick countdown only runs
    # while the engine ticks, so an idle engine (explicit capture on a
    # quiet silo, or a burst ending mid-capture) must not leave the
    # process-global jax trace open indefinitely
    capture_max_seconds: float = 60.0
    # jax.profiler trace root; "" = <system tmpdir>/orleans_tpu_profiles
    capture_dir: str = ""
    # captures per engine lifetime (triggered + explicit combined): a
    # pathological threshold must not fill the disk
    capture_limit: int = 8
    # memory ledger → overload containment: below this device-HBM
    # headroom ratio the ShedController floors its shed level (the
    # memory analog of the watchdog stall floor)
    memory_low_watermark: float = 0.1
    memory_shed_level: float = 0.5


@dataclass
class RebalanceConfig:
    """Closed-loop rebalance knobs (runtime/rebalancer.py): the
    actuator that consumes the attribution plane's HotSet / skew /
    ``slo.*`` burn signals and ACTS — batched live migration of hot
    grains off burning shards (engine.migrate_keys), cross-silo moves,
    and elastic scale-out/in state handoff.  Off by default: the
    controller changes placement, which benches/tests must opt into.
    Live-reloadable (silo.update_config re-pushes into the running
    controller)."""

    enabled: bool = False
    # decision cadence (seconds).  Each interval the controller diffs
    # the attribution plane's per-shard traffic sums, judges skew
    # against the trigger, and (past hysteresis) plans one move wave.
    interval_s: float = 0.5
    # interval max-shard traffic share that ARMS a move (uniform share
    # is 1/n_shards; the effective trigger never drops below
    # 1.25/n_shards so a balanced mesh can never be "burning")
    trigger_share: float = 0.25
    # consecutive over-trigger intervals before the first move — a
    # one-interval blip (a batch boundary, a compile stall) must not
    # shuffle grains
    hysteresis_intervals: int = 2
    # intervals to hold off after a move wave: the moved traffic needs
    # time to show up in the telemetry before re-judging (convergence,
    # not thrash)
    cooldown_intervals: int = 2
    # grains migrated per wave per arena — bounds both the move pause
    # and how much placement can churn per interval
    move_budget: int = 16
    # hot-set entries below this traffic share never move (moving cold
    # grains costs an epoch bump and buys nothing)
    min_grain_share: float = 0.0005
    # intervals with fewer messages than this are idle — no judgement,
    # hysteresis disarms (skew over noise traffic is meaningless)
    min_interval_msgs: int = 1024
    # when the latency SLO burn rate exceeds this, the share trigger
    # halves (floor 1.25/n_shards): a burning SLO justifies acting on
    # milder skew
    slo_burn_trigger: float = 1.0
    # ---- hot-grain replication (the lever past migration) ----
    # a single grain whose interval traffic share reaches this can no
    # longer be fixed by moving it (the burn relocates with it): if its
    # dominant methods are declared commutative the controller PROMOTES
    # it to replica rows across shards instead (0 disables replication
    # and restores the pure-migration planner)
    replicate_share: float = 0.15
    # replica rows a promotion spreads a hot grain across (clamped to
    # the mesh's shard count by the arena)
    max_replicas: int = 4
    # a replicated grain whose interval share falls below this is a
    # demotion candidate — its state folds back to one row
    demote_share: float = 0.02
    # consecutive below-demote_share intervals before the fold (the
    # replication analog of shrink patience: a hot grain's lull must
    # not flap promote/demote)
    demote_patience: int = 4
    # ---- cross-silo leg (clustered silos only) ----
    # move hot grains to a less-loaded PEER silo when this silo's SLO
    # burns and a peer has capacity headroom (placement overrides +
    # state-slab push, tensor/router.py)
    cross_silo: bool = False
    # peers whose reported arena occupancy ratio exceeds this are not
    # migration targets (satellite: the load report carries occupancy +
    # memory headroom so the controller sees REMOTE capacity)
    peer_occupancy_ceiling: float = 0.85
    # ---- elastic scale-out/in (tensor/router.py + silo.stop) ----
    # ring change (a silo JOINING): the old owner pushes moved keys'
    # state directly to the new owner (adopt_grains slab) instead of
    # evict-through-store-and-miss — state survives even storeless, and
    # the new owner never pays a first-touch store read
    handoff_migration: bool = True
    # graceful stop: migrate every resident grain out to its post-leave
    # ring owner BEFORE leaving membership (a draining silo hands its
    # residents over; survivors serve them without a miss)
    drain_migration: bool = True


@dataclass
class RemindersConfig:
    """(reference: GlobalConfiguration reminder service section :84)"""

    enabled: bool = True
    refresh_period: float = 30.0          # table re-read cadence
    # delegate reminders on tensor-arena grain types (with a
    # receive_reminder vector handler and narrow keys) to the device
    # timers plane instead of one asyncio timer each
    device_delegation: bool = True
    # wall-clock → engine-tick mapping for delegated reminders: one
    # engine tick is NOMINALLY this many seconds.  Delegated reminders
    # fire on the tick grid; the service's pump keeps ticks flowing at
    # this cadence while device timers are armed and the engine idles
    tick_seconds_hint: float = 0.01


@dataclass
class TensorEngineConfig:
    """TPU data-plane knobs (no reference analog — this is the rebuild's
    batched dispatch engine)."""

    enabled: bool = True
    tick_interval: float = 0.001          # min seconds between ticks
    max_rounds_per_tick: int = 4          # intra-tick call-chain rounds
    # adaptive tick sizing (SURVEY §7 hard-part 5): when a latency budget
    # is set, the engine's loop adjusts the accumulation interval between
    # ticks so that queue-wait + tick-service time stays inside the budget
    # (shrinks the batch when ticks run long, grows it back for throughput
    # when there is headroom).  0 disables adaptation (fixed tick_interval).
    target_tick_latency: float = 0.0
    tick_interval_min: float = 0.0002
    tick_interval_max: float = 0.05
    # continuous pipelined ticking (engine.TickPipeline): how many
    # dispatched ticks may be awaiting their device COMPLETION EVENT
    # before the loop backpressures on the oldest one.  1 = the legacy
    # serialized loop; 2 double-buffers — tick N+1's dispatch (and its
    # staged h2d) overlaps tick N's device execution, which donated
    # state buffers make safe.  Completion is observed event-driven (an
    # executor thread resolves a future on the tick's FENCE output the
    # moment the device signals), never by polling.  Live-reloadable.
    pipeline_depth: int = 2
    # the honest 10ms mode: pace the loop by completion events at the
    # minimum accumulation interval instead of the throughput-biased
    # adaptive/fixed sleep.  Live-reloadable.
    low_latency: bool = False
    # step/fused programs take the arena state columns as DONATED
    # inputs (jax donate_argnums), so XLA double-buffers in place and
    # back-to-back ticks never serialize on a host round-trip.  Off =
    # the undonated serial baseline the exactness A/B replays against;
    # rollback pins copy-before-donate.
    # A live toggle re-traces step programs (cause config_toggle).
    donate_state: bool = True
    # overlapped h2d: BatchInjector.stage() (and the auto-fuser's
    # window buffering) device_put the NEXT tick's injection slabs
    # while the current tick computes, so the transfer rides under
    # device execution instead of serializing before dispatch.
    overlap_h2d: bool = True
    # ring buffer of recent per-tick durations backing latency percentiles
    latency_window: int = 1024
    # tensor-path activation collection (reference: ActivationCollector
    # quantum + age limit): rows idle > collection_idle_ticks are evicted
    # (written back when a store is attached) every collection_every_ticks.
    # 0 disables automatic sweeps (collect_idle() remains callable).
    collection_idle_ticks: int = 0
    collection_every_ticks: int = 64
    # incremental collection (the reference collector never stalls the
    # message pump — ActivationCollector.cs:37): a sweep's victims drain
    # in bounded chunks interleaved between ticks, each slice capped at
    # this host-pause budget (seconds).  <= 0 runs the whole sweep in one
    # slice — the synchronous stop-the-world baseline for A/B
    # measurement.
    # Live-reloadable.
    collection_pause_budget_s: float = 0.005
    # victims written back per chunk: bounds both a single chunk's stall
    # (the budget is checked between chunks) and the device→host gather
    # size of one columnar write-back.  Live-reloadable.
    collection_chunk_rows: int = 65536
    # freed/high-water fragmentation ratio above which deactivation still
    # triggers a full per-shard repack (rows move, generation bumps —
    # the expensive path free-list reuse otherwise avoids).  <= 0 or > 1
    # disables threshold compaction (grow/reshard still repack).
    # Live-reloadable.
    compact_fragmentation_threshold: float = 0.75
    # padded host-batch buckets: a batch compiles at the smallest bucket
    # ≥ its size, so the ladder bounds both compile count and padding
    # waste (the old 65536 → 1M jump made a 200k-message batch pay 5×
    # its compute in padding)
    bucket_sizes: tuple = (256, 4096, 32768, 131072, 262144, 524288,
                           1 << 20)
    mesh_axis: str = "grains"
    # local devices the silo's engine spans: above 1 the silo builds a
    # 1-D mesh on mesh_axis over the first mesh_devices local devices,
    # so the arenas are sharded over them and cross-shard messages take
    # the device exchange; 1 builds no mesh (one device, no exchange)
    mesh_devices: int = 1
    # device-resident cross-shard routing (tensor/exchange.py): under a
    # mesh, device batches are bucketed by destination shard and moved
    # with ONE lax.all_to_all inside the compiled program, so the step
    # kernel's scatters are shard-local — the 8-device mesh runs as one
    # logical cluster with host slab transport reserved for true
    # cross-process hops.  Off = the implicit-collective baseline the
    # multichip bench A/Bs against.  Live-toggleable (fused windows
    # re-trace, cause config_toggle).
    cross_shard_exchange: bool = True
    # when the STRUCTURED formulation (bucket-by-shard + all_to_all)
    # actually runs: "auto" engages it only on a real accelerator
    # interconnect — on a host-virtual mesh (forced CPU device count:
    # one process, one memory, collectives are synchronized memcpies)
    # the structured region's per-op overhead exceeds the unstructured
    # scatter it replaces at every measured width (the multichip
    # bench's exchange_attribution carries the numbers), so auto plans
    # IDENTITY there: batches pass through untouched, delivery rides
    # the same implicit collectives as exchange-off, exactness
    # unconditional, and a sampled probe (exchange_probe_interval)
    # keeps the demand estimators + cross-traffic counters honest.
    # "always"/"never" force either side (exactness/overflow suites pin
    # "always" so the structured machinery stays covered on CPU rigs).
    # Live-reloadable: fused windows re-trace via the plan signature.
    exchange_structured: str = "auto"
    # when the structured path is disengaged, every Nth eligible batch
    # still runs a measure-only classification (stats parked, nothing
    # redelivered) so route.* counters and the occupancy estimates
    # stay fresh at 1/N of the classification cost
    exchange_probe_interval: int = 8
    # ---- occupancy-sized exchange buckets (tensor/exchange.py) ----
    # Size per-(src,dst) buckets from MEASURED per-site demand instead
    # of the worst-case formula: caps quantize onto a small ladder
    # ({2^k} ∪ {3·2^(k-1)}), grow immediately on overflow (the parked
    # redelivery path is the correctness net while the estimate lags a
    # traffic shift) and shrink only after exchange_shrink_patience calm
    # drains.  Off = every exchange pays the worst-case pad (the old
    # formulation, kept as the A/B baseline).
    exchange_occupancy_sizing: bool = True
    # granted cap = ladder_ceil(measured peak demand × headroom): the
    # skew allowance above the observed per-destination peak
    exchange_headroom: float = 1.5
    # consecutive drains below the current grant before a cap shrinks
    # (growth is immediate; shrink hysteresis stops compile flapping)
    exchange_shrink_patience: int = 4
    # per-DESTINATION exchange caps: instead of one scalar cap sized by
    # the max-over-destinations demand (one hot destination sizes every
    # lane's buckets), grant each destination its own ladder rung from
    # its measured demand — send width becomes sum-of-per-dest-caps and
    # the receive width a single rung over the worst shard's total
    # inbound.  "auto" engages the per-dest formulation only when it is
    # strictly narrower than the n·cap layout for the measured site
    # (symmetric demand keeps the legacy plan — zero regression);
    # "always"/"never" force either side.  Same grow-on-overflow /
    # shrink-after-patience / park-and-redeliver discipline, same
    # O(log) re-trace bound (re-quantization on any dest's rung change,
    # cause bucket_growth).
    exchange_per_dest: str = "auto"
    # fused source batches with static key sets are PACKED home-shard-
    # local on the host at window build (one gather outside the scan):
    # their cross-shard demand is zero by construction, so the source
    # leg's exchange short-circuits to the cap-0 classification pass —
    # no sort, no all_to_all, output width == input width
    exchange_align_sources: bool = True
    # unfused path: at round start, pre-dispatch the exchange for every
    # queued batch whose resolution is already cached, so the
    # all_to_all of tick t+1's cross traffic runs under tick t's
    # compute (exact — the exchange reads no arena state); the credit
    # shows as route.exchange_overlap_s
    exchange_overlap: bool = True
    # worst-case FALLBACK plan, used only before any demand observation
    # lands for a site: per-(src,dst) bucket floor (lanes) …
    exchange_pad_quantum: int = 256
    # … times the skew allowance over the uniform share L/n_shards
    # (2.0 absorbs 2x destination skew before lanes overflow into
    # redelivery; the engine re-delivers dropped lanes with their
    # original inject stamp, a fused window counts them as misses and
    # rolls back)
    exchange_capacity_factor: float = 2.0
    # device streams plane (tensor/streams_plane.py): registered
    # stream-subscription routes expand ON DEVICE — pull-mode (one
    # payload gather + one scatter-free segment reduction per tick)
    # when the publish pattern matches the bound key set, push-mode
    # CSR expansion otherwise.  Off = the host-expansion baseline the
    # streams bench A/Bs against (per-publish d2h + numpy adjacency
    # walk).  Live-toggleable: fused windows re-trace, cause
    # config_toggle.
    stream_plane: bool = True
    # cross-silo sender aggregation (tensor/router.py): slab fragments
    # bound for one (destination, type, method) within a drain cycle
    # merge into ONE wire frame, so receivers see stable batch sizes
    # instead of compile-churning fragment sizes.  Off only for A/B
    # measurement of the receivers' compile churn.
    slab_aggregation: bool = True
    # max parked optimistic miss-checks before a forced (synchronizing)
    # drain — bounds device memory pinned by deferred delivery checks
    miss_check_cap: int = 16
    # ---- durable state plane (tensor/checkpoint.py) ----
    # full-arena columnar checkpoint cadence (ticks; 0 = explicit
    # only): a consistent cut pinned at a tick boundary as ONE compiled
    # device copy per arena, then drained device→host in chunks BETWEEN
    # ticks — live traffic keeps running against the real columns while
    # the pin streams out (asynchronous-snapshot discipline).  Engaged
    # only when a SnapshotStore is attached.
    ckpt_full_every_ticks: int = 0
    # attribution-driven incremental deltas between fulls (ticks; 0 =
    # none): only rows whose traffic counts moved since the last
    # committed cut re-checkpoint — cold rows ride the last full.  A
    # generation change (rows moved) promotes the next delta to a full.
    ckpt_delta_every_ticks: int = 0
    # rows per drain chunk: one d2h gather of every field family per
    # chunk (bounds both a slice's stall and the gather's compile set)
    ckpt_chunk_rows: int = 65536
    # per-tick snapshot-drain pause budget (seconds); <= 0 drains the
    # whole pinned snapshot in one slice — the synchronous baseline the
    # durability bench A/Bs against.  Live-reloadable.
    ckpt_pause_budget_s: float = 0.005
    # device journal ring capacity per journaled (type, method) site
    # (lanes, pow2-rounded).  A batch that would overflow the ring
    # seals the open segment first (counted journal.ring_overflows);
    # a batch wider than the ring grows it.
    journal_ring_lanes: int = 65536
    # journal segment seal cadence (ticks; 0 = seal only at
    # checkpoints / ring overflow / explicit flush).  Sealing is the
    # durability acknowledgement point: ring lanes beyond the last
    # sealed segment are the documented loss window of a hard kill.
    journal_flush_every_ticks: int = 0
    # recover from the snapshot store's manifest at silo startup
    # (runtime/silo.py start: restore arenas + fold-replay the journal
    # tail BEFORE serving traffic); off = manual recover() only
    durable_recovery: bool = True
    # journal tail fold-replay window (ticks): recover() groups runs of
    # consecutive journaled ticks with a consistent per-site signature
    # into ONE fused device window (tensor/fused.py stacked-rows mode)
    # instead of a per-tick engine call each, rolling back (exactly) to
    # the per-tick path on any miss.  <= 1 replays per-tick always.
    # Fused replay is also skipped while timers are armed at the cut
    # (fused windows don't harvest timers) or a router is attached.
    recover_fused_window: int = 64
    # terminal re-anchor policy after recover(): "sync" writes a fresh
    # full checkpoint inside recover (the pre-PR-18 behavior — restore
    # time includes a full snapshot drain), "defer" leaves the old
    # recovery point in place and lets the periodic cadence re-anchor;
    # correctness is unchanged (a second crash replays the same
    # journal tail idempotently from the old cut).
    recover_reanchor: str = "defer"
    # periodic arena write-back cadence (ticks; 0 = only explicit
    # checkpoints): bounds the state-loss window when a silo is KILLED
    # (no goodbye, no graceful handoff write-back) to at most this many
    # ticks of updates — survivors re-activate the dead silo's keys from
    # the last periodic checkpoint.  Each checkpoint is a full
    # device→host read of every live row, so small values trade
    # throughput for a tighter loss bound.
    checkpoint_every_ticks: int = 0
    # auto-fusion (tensor/autofuse.py): after auto_fusion_ticks
    # consecutive ticks with an identical injection pattern the engine
    # transparently compiles the steady tick into a fused window of
    # auto_fusion_window ticks, rolling back (exactly) on any miss.
    # 0 disables detection.
    auto_fusion_ticks: int = 16
    auto_fusion_window: int = 16
    # rollback hysteresis: after this many rolled-back windows for one
    # signature the pattern is banned (until ring/generation change) —
    # repeated rollbacks mean the workload regularly touches cold keys
    # and fusion only adds snapshot + replay cost
    auto_fusion_max_rollbacks: int = 3
    # windows per exactness-verification sync: the device-side miss
    # counter is read once per this many windows (a completion
    # observation measured ~100ms on the pre-PR-1 chip rig, the one this
    # was tuned on), so a rollback replays up to
    # verify_windows * window ticks; 1 = verify every window
    auto_fusion_verify_windows: int = 4
    # idle grace before a partially-filled window replays unfused: if no
    # new work arrives for this long the engine's loop drains the buffer
    # so mid-window ticks never strand awaiting an explicit flush()
    auto_fusion_idle_flush: float = 0.02
    # handoff fence (tensor/router.py): max seconds a silo defers unseen-
    # key activation after a ring change while awaiting peers' write-back
    # releases; a dead/stalled peer must not wedge the cluster
    handoff_fence_timeout: float = 2.0
    # device timers plane (tensor/timers_plane.py): per-tick harvest of
    # the hierarchical timing wheel.  Off = the A/B baseline the timers
    # bench measures against (armed timers stop firing while off; the
    # wheel catches up on re-enable).  Live-reloadable.
    timers_plane: bool = True
    # wheel level widths in bits, lowest first: (8, 6, 6) = 256 one-tick
    # buckets, 64×256-tick, 64×16384-tick (~1M-tick horizon before the
    # overflow list).  More L0 bits = cheaper cascades, more idle bucket
    # memory.  Takes effect for wheels built after the change.
    timers_wheel_bits: tuple = (8, 6, 6)
    # tick-jump size beyond which advance_to rebuilds the wheel from the
    # live slot mirrors (O(armed)) instead of stepping tick-by-tick —
    # idle gaps and fused windows land here
    timers_catchup_jump: int = 4096
    # arm/cancel rows the delta op log may hold between checkpoint cuts;
    # overflow promotes the next timers export to a full (bounded
    # memory, same discipline as the journal ring)
    timers_ops_cap: int = 1 << 18


@dataclass
class SiloConfig:
    name: str = "silo"
    # DeploymentLoadPublisher cadence (reference: GlobalConfiguration
    # DeploymentLoadPublisherRefreshTime); 0 disables the broadcast
    load_publish_period: float = 1.0
    # adaptive directory-cache maintenance cadence (reference:
    # AdaptiveDirectoryCacheMaintainer.cs:34); 0 disables the loop
    directory_cache_maintenance_period: float = 5.0
    # watchdog health-check cadence (reference: Watchdog.cs
    # healthCheckPeriod); 0 disables the watchdog
    watchdog_period: float = 5.0
    # False = transient observer member (admin CLI): joins membership but
    # takes no grain placements and no ring ranges
    host_grains: bool = True
    # cadence of statistics publication to registered publishers
    # (reference: StatisticsCollectionLevel / LogStatistics period)
    statistics_report_period: float = 30.0
    # run a client gateway on this silo (reference: NodeConfiguration
    # ProxyGatewayEndpoint — silos without one don't accept clients and
    # are not advertised by gateway list providers)
    gateway_enabled: bool = True
    # warm-standby: name of the primary silo this silo tails (log
    # shipping over the primary's SnapshotStore — committed fulls,
    # deltas, and sealed journal segments; see runtime/silo.py
    # arm_standby).  Empty = not a standby.  A standby adopts the
    # primary's checkpoints as they commit and promotes (fence + replay
    # the staged journal tail) when membership declares the primary
    # DEAD.  The store itself is attached via silo.arm_standby(...) at
    # setup — it is a live object, not config.
    standby_for: str = ""
    # standby manifest poll cadence (seconds)
    standby_poll_period: float = 0.05
    liveness: LivenessConfig = field(default_factory=LivenessConfig)
    directory: DirectoryConfig = field(default_factory=DirectoryConfig)
    collection: CollectionConfig = field(default_factory=CollectionConfig)
    messaging: MessagingConfig = field(default_factory=MessagingConfig)
    rpc: RpcConfig = field(default_factory=RpcConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    tracing: TracingConfig = field(default_factory=TracingConfig)
    metrics: MetricsConfig = field(default_factory=MetricsConfig)
    profiler: ProfilerConfig = field(default_factory=ProfilerConfig)
    rebalance: RebalanceConfig = field(default_factory=RebalanceConfig)
    reminders: RemindersConfig = field(default_factory=RemindersConfig)
    tensor: TensorEngineConfig = field(default_factory=TensorEngineConfig)
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "SiloConfig":
        import typing
        hints = typing.get_type_hints(cls)  # resolve string annotations
        kwargs: Dict[str, Any] = {}
        for f in dataclasses.fields(cls):
            if f.name not in d:
                continue
            v = d[f.name]
            ftype = hints.get(f.name, f.type)
            if dataclasses.is_dataclass(ftype) and isinstance(v, dict):
                kwargs[f.name] = ftype(**v)
            else:
                kwargs[f.name] = v
        return cls(**kwargs)


@dataclass
class ClientConfig:
    """(reference: ClientConfiguration.cs)"""

    response_timeout: float = 30.0
    gateway_list: list = field(default_factory=list)
    # gateway control-frame reply wait (handshake-adjacent ops: observer
    # registration etc.); hoisted from the hard-coded 10.0 in the TCP
    # gateway handle so tests/chaos plans can tighten it
    control_timeout: float = 10.0
    # client-side transient-resend containment (parity with the silo's
    # ResilienceConfig backoff/budget knobs)
    max_resend_count: int = 3
    backoff_enabled: bool = True
    backoff_base: float = 0.02
    backoff_cap: float = 1.0
    retry_budget_capacity: float = 32.0
    retry_budget_fill: float = 0.1
    # client-edge tracing (parity with the silo's TracingConfig): the
    # client is a trace INGRESS — it mints trace ids head-sampled at
    # this rate; error/timeout spans record regardless
    trace_enabled: bool = True
    trace_sample_rate: float = 0.01
    # batched RPC fastpath over TCP gateways: eligible calls coalesce
    # into one calls-frame per event-loop iteration (negotiated
    # (type, method) dictionary + zero-copy codec); ineligible calls
    # (string/uuid keys, ambient contexts, one-off control ops) ride
    # the per-message frames unchanged.  Sampled traces RIDE the
    # fastpath via the frame's per-lane trace column — sampling never
    # changes the executed path
    rpc_fastpath: bool = True
