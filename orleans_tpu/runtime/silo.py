"""Silo: assembles and runs every runtime component.

Parity: reference Silo (reference: src/OrleansRuntime/Silo.cs:59 —
constructor wiring :151-337, startup ordering :414-577, graceful stop
:642-770, FastKill :776, system-target registration :339, status machine
SystemStatus.cs) and SiloHost.cs.

One silo == one asyncio event loop's worth of control plane + (optionally)
one slice of the TPU device mesh for the tensor data plane.  Multiple silos
may share a process and loop (the in-process test cluster — reference:
TestingSiloHost) or run one per host over the DCN transport.
"""

from __future__ import annotations

import asyncio
import uuid
from enum import Enum
from typing import Any, Callable, Dict, List, Optional

from orleans_tpu.config import SiloConfig
from orleans_tpu.core.factory import GrainFactory
from orleans_tpu.ids import (
    GrainId,
    SiloAddress,
    SystemTargetCodes,
)
from orleans_tpu.runtime.catalog import Catalog
from orleans_tpu.runtime.directory import LocalGrainDirectory, RemoteGrainDirectory
from orleans_tpu.runtime.dispatcher import Dispatcher
from orleans_tpu.runtime.messaging import (
    Category,
    Direction,
    Message,
    MessageCenter,
    ResponseKind,
)
from orleans_tpu.runtime.placement_directors import PlacementDirectorsManager
from orleans_tpu.runtime.ring import VirtualBucketsRing
from orleans_tpu.runtime.runtime_client import CallbackData, InsideRuntimeClient
from orleans_tpu.runtime.storage import StorageProvider
from orleans_tpu.stats import SiloMetrics
from orleans_tpu.tracing import TraceLogger


class SiloStatus(Enum):
    """(reference: SystemStatus.cs / SiloStatus)"""

    CREATED = "created"
    JOINING = "joining"
    ACTIVE = "active"
    SHUTTING_DOWN = "shutting_down"
    STOPPING = "stopping"
    DEAD = "dead"


_SYSTEM_TARGET_CODES: Dict[str, int] = {
    "directory": int(SystemTargetCodes.DIRECTORY_SERVICE),
    "silo_control": int(SystemTargetCodes.SILO_CONTROL),
    "client_registrar": int(SystemTargetCodes.CLIENT_OBSERVER_REGISTRAR),
    "catalog": int(SystemTargetCodes.CATALOG),
    "membership": int(SystemTargetCodes.MEMBERSHIP_ORACLE),
    "reminders": int(SystemTargetCodes.REMINDER_SERVICE),
    "type_manager": int(SystemTargetCodes.TYPE_MANAGER),
    "provider_manager": int(SystemTargetCodes.PROVIDER_MANAGER),
    "load_publisher": int(SystemTargetCodes.DEPLOYMENT_LOAD_PUBLISHER),
    "stream_pulling": int(SystemTargetCodes.STREAM_PULLING_MANAGER),
    "vector_router": int(SystemTargetCodes.VECTOR_ROUTER),
}
_CODE_TO_NAME = {v: k for k, v in _SYSTEM_TARGET_CODES.items()}


def engine_mesh(config):
    """The mesh the silo's tensor engine spans: 1-D on
    ``config.mesh_axis`` over the first ``config.mesh_devices`` local
    devices, or None at 1 (the engine then runs on one device)."""
    want = int(config.mesh_devices)
    if want < 1:
        raise ValueError(f"tensor.mesh_devices must be at least 1, "
                         f"got {want}")
    if want == 1:
        return None
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.local_devices()
    if len(devices) < want:
        raise ValueError(f"tensor.mesh_devices asks for {want} devices, "
                         f"but {len(devices)} local devices are visible")
    return Mesh(np.array(devices[:want]), (config.mesh_axis,))


class _CatalogTarget:
    """Catalog system target: remote existence checks + admin ops
    (reference: Catalog as SystemTarget, Constants catalog=14)."""

    def __init__(self, silo: "Silo") -> None:
        self.silo = silo

    async def has_activation(self, addr) -> bool:
        from orleans_tpu.runtime.activation import ActivationState
        act = self.silo.catalog.directory.by_activation.get(addr.activation)
        return act is not None and act.state in (ActivationState.VALID,
                                                 ActivationState.ACTIVATING)

    async def activation_count(self) -> int:
        return len(self.silo.catalog.directory)

    async def activate_grain(self, grain_id) -> bool:
        """Proactive activation — the receive half of host-grain live
        migration (catalog.migrate_activation): the grain's new home
        activates it (directory registers here) before any caller's
        next message needs a placement decision."""
        act = await self.silo.catalog.get_or_create_activation(grain_id)
        return act is not None


class Silo:
    """(reference: Silo.cs:59)"""

    def __init__(self, config: Optional[SiloConfig] = None,
                 name: str = "silo", port: int = 0,
                 storage_providers: Optional[Dict[str, StorageProvider]] = None,
                 fabric=None, membership_table=None,
                 reminder_table=None, host: Optional[str] = None,
                 ) -> None:
        self.config = config or SiloConfig(name=name)
        self.name = self.config.name if config else name
        # host defaults to the silo NAME (an in-proc label); TCP fabrics
        # pass a routable host because SiloAddress.host:port IS the
        # endpoint peers dial (reference: SiloAddress is IP:port+gen).
        # Routable endpoints get time-based generations so incarnations
        # stay distinct ACROSS processes (new_endpoint docstring).
        self.address = (SiloAddress.new_endpoint(host, port)
                        if host is not None
                        else SiloAddress.new_local(host=self.name, port=port))
        self.status = SiloStatus.CREATED
        self.logger = TraceLogger(f"silo.{self.name}")
        self.metrics = SiloMetrics()
        # unified metrics plane (orleans_tpu/metrics.py): the typed,
        # catalogued registry every component's counters/gauges/latency
        # histograms collect into; its snapshot piggybacks on the load
        # publisher broadcast and merges cluster-wide in snapshot()
        from orleans_tpu.metrics import MetricsRegistry
        self.metrics_registry = MetricsRegistry(source=self.name)
        self._ledger_publish_tick = -(1 << 30)  # last d2h-fetch tick
        # HotSet refreshed on the cadence-gated attribution publish —
        # the broadcast path serves this copy instead of paying an
        # ungated device fetch per publisher interval
        self._hot_set_cache: Optional[List[Dict[str, Any]]] = None

        # distributed tracing plane (orleans_tpu/spans.py): hop spans +
        # batched engine-tick spans + the crash flight recorder.  Built
        # FIRST — the resilience plane's dead-letter hook and every
        # runtime component record through it.
        from orleans_tpu.spans import SpanRecorder, TimelineRecorder
        tr = self.config.tracing
        self.spans = SpanRecorder(
            self.name, enabled=tr.enabled, sample_rate=tr.sample_rate,
            flight_capacity=tr.flight_recorder_capacity,
            breaker_capacity=tr.breaker_transition_capacity)
        # cluster timeline plane (orleans_tpu/timeline.py): every
        # committed span + lifecycle event + interval metric delta
        # appends to this bounded per-silo log; a collector merges the
        # logs onto a common clock and exports TIMELINE.json + Perfetto
        self.spans.timeline = TimelineRecorder(
            self.name, capacity=tr.timeline_capacity,
            enabled=tr.enabled and tr.timeline_enabled)
        # last-published counter totals for the timeline's interval
        # metric deltas (collect_metrics cadence)
        self._timeline_totals: Dict[str, float] = {}
        # unified incident evidence: the newest bundles dumped by any
        # trip (fence, watchdog, SLO burn, chaos invariant)
        from collections import deque as _deque
        self.incidents: Any = _deque(maxlen=8)
        self._slo_was_healthy = True  # SLO breach edge-trigger state

        # overload containment & failure isolation plane (PR: resilience)
        # — built BEFORE the components that consult it
        from orleans_tpu.limits import ShedController
        from orleans_tpu.resilience import (
            BreakerBoard,
            DeadLetterRing,
            RetryBudget,
        )
        r = self.config.resilience
        self.dead_letters = DeadLetterRing(r.dead_letter_capacity)
        # every terminal drop leaves an ALWAYS-ON span (third ledger next
        # to the metrics counter and the dead-letter record)
        self.dead_letters.on_record.append(self._on_dead_letter)
        self.breakers = BreakerBoard(
            enabled=r.breaker_enabled,
            failure_threshold=r.breaker_failure_threshold,
            reset_timeout=r.breaker_reset_timeout,
            half_open_probes=r.breaker_half_open_probes)
        self.breakers.on_transition.append(self._on_breaker_transition)
        self.retry_budget = RetryBudget(
            capacity=r.retry_budget_capacity,
            fill_rate=r.retry_budget_fill,
            enabled=r.backoff_enabled)
        self.shed_controller = ShedController(
            enabled=r.shed_enabled,
            queue_soft=r.shed_queue_soft, queue_hard=r.shed_queue_hard,
            ttl_reference=r.shed_ttl_reference,
            sample_period=r.shed_sample_period,
            stall_level=r.shed_stall_level,
            stall_window=r.shed_stall_window,
            depth_fn=self._pending_request_depth)

        # construction order mirrors reference Silo ctor :151-337
        self.ring = VirtualBucketsRing(
            self.address, self.config.directory.buckets_per_silo)
        if not self.config.host_grains:
            # non-hosting observer (admin CLI): takes NO ring ranges — its
            # own ring holds only the real hosts it learns via membership,
            # so directory/placement ownership never lands here
            self.ring.remove_silo(self.address)
        self.message_center = MessageCenter(self.address)
        self.message_center.metrics = self.metrics
        self.grain_directory = LocalGrainDirectory(self)
        self.catalog = Catalog(self)
        self.catalog.age_limit = self.config.collection.default_age_limit
        self.runtime_client = InsideRuntimeClient(self)
        self.runtime_client.response_timeout = \
            self.config.messaging.response_timeout
        self.runtime_client.max_resend_count = \
            self.config.messaging.max_resend_count
        self.grain_directory.cache.max_size = self.config.directory.cache_size
        self.dispatcher = Dispatcher(self)
        self.dispatcher.perform_deadlock_detection = \
            self.config.messaging.deadlock_detection
        # batched host RPC plane (runtime/rpc.py): ingress ring +
        # coalesced invoke windows for hosted-client/gateway calls
        from orleans_tpu.runtime.rpc import RpcCoalescer, RpcFabric
        self.rpc = RpcCoalescer(self)
        # batched silo→silo fabric: per-destination egress rings drained
        # into sectioned rpc frames (the coalescer's intra-cluster twin)
        self.rpc_fabric = RpcFabric(self)
        self.placement_manager = PlacementDirectorsManager(self)
        self.factory = GrainFactory()
        self.max_forward_count = self.config.messaging.max_forward_count

        self.message_center.dispatcher = self.dispatcher
        self.message_center.breakers = self.breakers
        self.message_center.dead_letters = self.dead_letters
        self.message_center.rpc_fabric = self.rpc_fabric

        # providers (reference: StorageProviderManager; Silo.cs:478-484)
        self.storage_providers: Dict[str, StorageProvider] = \
            dict(storage_providers or {})
        self.stream_providers: Dict[str, Any] = {}
        # bootstrap providers run once the runtime is live (reference:
        # BootstrapProviderManager, Silo.cs:542-552); name → (instance,
        # config).  Statistics publishers get the periodic metrics
        # snapshot (reference: StatisticsProviderManager + LogStatistics)
        self.bootstrap_providers: Dict[str, tuple] = {}
        self.statistics_publishers: Dict[str, Any] = {}
        self._stats_report_task: Optional[asyncio.Task] = None
        # DI analog: named services registered by the startup hook and
        # resolved by grains via Grain.service() (reference:
        # ConfigureStartupBuilder.cs:40)
        self.services: Dict[str, Any] = {}
        # live-reload subscribers (reference: OnConfigChange hooks)
        self._config_listeners: List[Callable[[SiloConfig], Any]] = []

        # system targets (reference: Silo.CreateSystemTargets :339)
        self.system_targets: Dict[str, Any] = {}
        self.register_system_target("directory",
                                    RemoteGrainDirectory(self.grain_directory))
        if self.config.gateway_enabled:
            from orleans_tpu.runtime.gateway import Gateway
            self.register_system_target("gateway", Gateway(self))
        self.register_system_target("catalog", _CatalogTarget(self))
        from orleans_tpu.runtime.management import SiloControl
        self.register_system_target("silo_control", SiloControl(self))

        # identity for calls made from non-grain contexts attached to this
        # silo (tests, hosted client) — reference: client GrainId
        self.client_grain_id = GrainId.client(uuid.uuid4())

        # cluster fabric + membership (single-silo when both are None:
        # the ring is the membership view)
        self._fabric = fabric
        self._bound_transport = None
        self.gateway_acceptor = None
        self.gateway_port = 0  # client-facing port (0 = in-proc only)
        self.membership_oracle = None
        if membership_table is not None:
            from orleans_tpu.runtime.membership import MembershipOracle
            self.membership_oracle = MembershipOracle(
                self, membership_table, self.config.liveness)
        self.reminder_service = None
        if self.config.reminders.enabled:
            from orleans_tpu.runtime.reminders import (
                GrainBasedReminderTable,
                InMemoryReminderTable,
                LocalReminderService,
            )
            if reminder_table is None:
                # clustered silos without an explicit table share rows via
                # the table *grain* (reference: GrainBasedReminderTable dev
                # mode) — a private in-memory table would strand reminders
                # whose ring owner isn't the registering silo
                reminder_table = (GrainBasedReminderTable(self)
                                  if fabric is not None
                                  else InMemoryReminderTable())
            self.reminder_service = LocalReminderService(
                self, reminder_table,
                refresh_period=self.config.reminders.refresh_period)
        # watchdog (reference: Watchdog.cs:32, wired at Silo.cs:261,366)
        self.watchdog = None
        if self.config.watchdog_period > 0:
            from orleans_tpu.runtime.watchdog import Watchdog
            self.watchdog = Watchdog(self, self.config.watchdog_period)

        # deployment load broadcast → power-of-k placement (reference:
        # DeploymentLoadPublisher.cs:39); only meaningful in a cluster
        self.load_publisher = None
        if fabric is not None and self.config.load_publish_period > 0:
            from orleans_tpu.runtime.load_publisher import (
                DeploymentLoadPublisher,
            )
            self.load_publisher = DeploymentLoadPublisher(
                self, self.config.load_publish_period)
        # adaptive directory-cache maintainer: refresh/promote hot cache
        # lines, drop moved/stale ones (reference:
        # AdaptiveDirectoryCacheMaintainer.cs:34)
        self.cache_maintainer = None
        if fabric is not None \
                and self.config.directory_cache_maintenance_period > 0:
            from orleans_tpu.runtime.directory import (
                AdaptiveDirectoryCacheMaintainer,
            )
            self.cache_maintainer = AdaptiveDirectoryCacheMaintainer(
                self.grain_directory,
                period=self.config.directory_cache_maintenance_period)
        self._stop_callbacks: List[Callable[[], Any]] = []

        # elasticity: membership-driven ring changes re-assert directory
        # entries + client routes (reference: GrainDirectoryHandoffManager)
        self.ring.subscribe(lambda *_: self._on_ring_changed())

        # the TPU data plane (SURVEY.md §7 design stance)
        if self.config.tensor.enabled:
            from orleans_tpu.tensor.engine import TensorEngine
            self.tensor_engine = TensorEngine(self, self.config.tensor,
                                              mesh=engine_mesh(
                                                  self.config.tensor),
                                              metrics=self.config.metrics,
                                              profiler=self.config.profiler)
        else:
            self.tensor_engine = None
        # durable state plane: the last startup recovery's stats (None
        # until a recovery ran — tensor/checkpoint.py recover())
        self.last_recovery: Optional[Dict[str, Any]] = None
        # warm standby (tensor/checkpoint.py StandbyTailer): armed via
        # arm_standby(store, primary=...); polls the primary's snapshot
        # store on config.standby_poll_period and promotes on the
        # primary's DEAD declaration.  last_promotion holds promote()'s
        # stats (the measured RTO) once it fired.
        self.standby = None
        self._standby_primary: str = self.config.standby_for
        self._standby_task: Optional[asyncio.Task] = None
        self.last_promotion: Optional[Dict[str, Any]] = None
        if self.tensor_engine is not None:
            # promotion fence trip: a standby claimed our store — this
            # silo must never acknowledge another write (it would be
            # lost to the promoted range owner).  Fast-kill, exactly
            # like the crash the standby already covers.
            self.tensor_engine.checkpointer.on_fenced = self._fenced_kill
        # closed-loop rebalance (runtime/rebalancer.py): consumes the
        # attribution plane's HotSet/skew/slo.* signals and ACTS via
        # batched live migration.  Always constructed with an engine so
        # the config toggle can flip live; the loop itself gates on
        # config.rebalance.enabled every interval.
        self.rebalancer = None
        if self.tensor_engine is not None:
            from orleans_tpu.runtime.rebalancer import RebalanceController
            self.rebalancer = RebalanceController(self)
        # cross-silo vector data plane: clustered silos partition vector
        # batches by ring owner and ship remote partitions as slabs
        # (tensor/router.py; single-activation enforcement)
        self.vector_router = None
        if self.tensor_engine is not None and fabric is not None:
            from orleans_tpu.tensor.router import VectorRouter
            self.vector_router = VectorRouter(self)
            self.register_system_target("vector_router", self.vector_router)
        elif fabric is not None:
            # tensor-less clustered silo: peers' handoff fences still
            # await this silo's release on every ring change — answer
            # with a stub that releases trivially (it owns no rows)
            from orleans_tpu.tensor.router import HandoffFenceStub
            self.register_system_target("vector_router",
                                        HandoffFenceStub(self))

    # ================= lifecycle (reference: Silo.cs :414,:642) ============

    async def start(self) -> None:
        self.status = SiloStatus.JOINING
        if self._fabric is not None:
            bound = self._fabric.attach(self)
            if asyncio.iscoroutine(bound):  # TCP fabrics bind sockets
                bound = await bound
            self._bound_transport = bound
            self.message_center.transport = self._bound_transport
        # TCP client edge: gateway silos with a routable endpoint listen
        # for clients on a dedicated port (reference: ProxyGatewayEndpoint,
        # GatewayAcceptor.cs:32); the port is advertised via membership
        if (self.config.gateway_enabled
                and getattr(self._bound_transport, "transport", None)
                is not None):
            from orleans_tpu.runtime.gateway import GatewayAcceptor
            self.gateway_acceptor = GatewayAcceptor(self,
                                                    host=self.address.host)
            await self.gateway_acceptor.start()
            self.gateway_port = self.gateway_acceptor.port
        for name, provider in self.storage_providers.items():
            await provider.init(name, {})
        self.catalog.start_collector(self.config.collection.collection_quantum)
        if self.membership_oracle is not None:
            await self.membership_oracle.start()
        if self.reminder_service is not None:
            await self.reminder_service.start()
        for provider in self.stream_providers.values():
            start = getattr(provider, "start", None)
            if start is not None:
                await start()
        if self.tensor_engine is not None:
            ck = self.tensor_engine.checkpointer
            if ck.enabled and self.config.tensor.durable_recovery:
                # durable state plane: rebuild arenas from the latest
                # committed recovery point + fold-replay the journal
                # tail BEFORE serving traffic (tensor/checkpoint.py) —
                # crash recovery is a startup stage, like storage init
                self.last_recovery = await ck.recover()
            self.tensor_engine.start()
        if self.standby is not None and self._standby_task is None:
            self._standby_task = asyncio.get_running_loop().create_task(
                self._standby_poll_loop())
        if self.load_publisher is not None:
            self.load_publisher.start()
        if self.cache_maintainer is not None:
            self.cache_maintainer.start()
        if self.rebalancer is not None:
            self.rebalancer.start()
        # bootstrap providers: app startup logic inside the live silo
        # (reference: Silo.cs:542-552 — after stream providers start)
        for name, (provider, cfg) in self.bootstrap_providers.items():
            await provider.init(name, self, cfg)
        if self.statistics_publishers:
            for name, pub in self.statistics_publishers.items():
                await pub.init(self.name)
            self._stats_report_task = asyncio.get_running_loop().create_task(
                self._stats_report_loop())
        if self.watchdog is not None:
            self.watchdog.register(self.membership_oracle)
            self.watchdog.register(self.reminder_service)
            self.watchdog.register(self.tensor_engine)
            self.watchdog.start()
        self.status = SiloStatus.ACTIVE
        self.spans.timeline.lifecycle("join", address=str(self.address),
                                      gateway_port=self.gateway_port)
        self.logger.info(f"silo {self.address} active")

    async def stop(self, graceful: bool = True) -> None:
        """(reference: Silo.Terminate :642-770 graceful / FastKill :776)"""
        self.status = SiloStatus.SHUTTING_DOWN if graceful else SiloStatus.STOPPING
        self.spans.timeline.lifecycle("drain" if graceful else "stop",
                                      address=str(self.address))
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.load_publisher is not None:
            self.load_publisher.stop()
        if self.cache_maintainer is not None:
            self.cache_maintainer.stop()
        if self.rebalancer is not None:
            self.rebalancer.stop()
        if self._standby_task is not None:
            self._standby_task.cancel()
            self._standby_task = None
        if self.tensor_engine is not None:
            await self.tensor_engine.stop(drain=graceful)
        # reminder timers must die on ANY stop — a zombie service would
        # keep mutating the shared durable table after "death"
        if self.reminder_service is not None:
            await self.reminder_service.stop()
        # pulling agents likewise must stop on ANY shutdown, else a zombie
        # agent keeps consuming shared queues after "death"
        for provider in self.stream_providers.values():
            stop = getattr(provider, "stop", None)
            if stop is not None:
                await stop()
        if graceful:
            await self.catalog.deactivate_all()
            if self.tensor_engine is not None \
                    and self.tensor_engine.store is not None:
                # arena handoff through storage, BEFORE the membership
                # goodbye: the engine is already stopped and drained, so
                # this write-back is the rows' final state AND it is
                # durable before any peer learns of the departure — a peer
                # that reroutes and re-activates our keys on first touch
                # always reads this checkpoint, never pre-handoff state
                # (reference: graceful Shutdown deactivates all grains
                # through their storage bridge, Silo.cs:642-770)
                await self.tensor_engine.checkpoint()
            if self.tensor_engine is not None \
                    and self.tensor_engine.checkpointer.enabled:
                # durable state plane: seal the journal + commit a final
                # full snapshot so the recovery point equals the
                # terminal state exactly (a graceful stop loses nothing)
                self.tensor_engine.checkpointer.checkpoint_full()
            if (self.vector_router is not None
                    and self.config.rebalance.drain_migration
                    and hasattr(self.vector_router, "drain_migrate_out")):
                # elastic scale-IN: migrate every resident grain to its
                # post-leave ring owner BEFORE the membership goodbye —
                # survivors adopt the state directly (no first-touch
                # store miss; works even storeless).  The checkpoint
                # above remains the durable net if a push is lost.
                await self.vector_router.drain_migrate_out()
            if self.membership_oracle is not None:
                await self.membership_oracle.leave()
        self.catalog.stop_collector()
        for cb in self._stop_callbacks:
            res = cb()
            if asyncio.iscoroutine(res):
                await res
        if self._stats_report_task is not None:
            self._stats_report_task.cancel()
            self._stats_report_task = None
        for name, pub in self.statistics_publishers.items():
            try:
                await pub.report(self.name, self.metrics.snapshot())
            except Exception:  # noqa: BLE001 — stats must not block stop
                pass
            try:
                await pub.close()
            except Exception:  # noqa: BLE001 — a failed final report must
                pass           # not leak the publisher's resources
        for _, (provider, _cfg) in self.bootstrap_providers.items():
            try:
                await provider.close()
            except Exception:  # noqa: BLE001 — close must not block stop
                self.logger.warn("bootstrap provider close failed",
                                 code=2802)
        for provider in self.storage_providers.values():
            await provider.close()
        if self.gateway_acceptor is not None:
            self.gateway_acceptor.close()
        if self._bound_transport is not None:
            if graceful:
                # flush the fabric's egress rings, then the outbound
                # sender queues, so in-flight responses reach their
                # callers before the sockets die
                try:
                    await self.rpc_fabric.wait_idle()
                except Exception:  # noqa: BLE001 — a wedged flush must
                    pass           # not block shutdown
                drain = getattr(self._bound_transport, "drain", None)
                if drain is not None:
                    await drain()
            self.rpc_fabric.close_nowait()
            self._bound_transport.close()
        self.status = SiloStatus.DEAD

    def kill(self) -> None:
        """Hard kill for tests: no deactivations, no handoff
        (reference: Silo.FastKill :776; TestingSiloHost.KillSilo)."""
        self.status = SiloStatus.DEAD
        self.spans.timeline.lifecycle("kill", address=str(self.address))
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.load_publisher is not None:
            self.load_publisher.stop()
        if self.cache_maintainer is not None:
            self.cache_maintainer.stop()
        if self._stats_report_task is not None:
            self._stats_report_task.cancel()
            self._stats_report_task = None
        if self._standby_task is not None:
            self._standby_task.cancel()
            self._standby_task = None
        self.catalog.stop_collector()
        for provider in self.stream_providers.values():
            k = getattr(provider, "kill", None)
            if k is not None:
                k()
        if self.reminder_service is not None:
            self.reminder_service.kill()
        if self.membership_oracle is not None:
            self.membership_oracle.kill()
        if self.gateway_acceptor is not None:
            self.gateway_acceptor.close()
        self.rpc_fabric.close_nowait()
        if self._bound_transport is not None:
            self._bound_transport.close()

    def on_stop(self, cb: Callable[[], Any]) -> None:
        self._stop_callbacks.append(cb)

    # ================= warm standby ========================================

    def arm_standby(self, store, primary: str = "") -> None:
        """Make this silo a warm standby: tail ``store`` (the primary's
        snapshot store — log shipping over the existing durable plane,
        no new wire protocol) and promote when membership declares the
        primary DEAD.  ``primary`` names the silo whose death triggers
        promotion (falls back to config.standby_for; empty = any DEAD
        declaration promotes).  Callable before or after start()."""
        if self.tensor_engine is None:
            raise RuntimeError("standby needs a tensor engine")
        from orleans_tpu.tensor.checkpoint import StandbyTailer
        self.standby = StandbyTailer(self.tensor_engine, store)
        if primary:
            self._standby_primary = primary
        if self.status == SiloStatus.ACTIVE \
                and self._standby_task is None:
            self._standby_task = asyncio.get_running_loop().create_task(
                self._standby_poll_loop())

    async def _standby_poll_loop(self) -> None:
        period = max(self.config.standby_poll_period, 0.001)
        while self.standby is not None and not self.standby.promoted:
            try:
                self.standby.poll()
            except Exception:  # noqa: BLE001 — tailing must outlive
                # transient store hiccups; the tailer re-bases itself
                self.logger.warn("standby poll failed", code=2810)
            await asyncio.sleep(period)

    async def _promote_standby(self, dead: "SiloAddress") -> None:
        standby, self._standby_task = self.standby, None
        if standby is None or standby.promoted:
            return
        self.last_promotion = await standby.promote(owner=self.name)
        self.last_promotion["for"] = str(dead)
        self.spans.timeline.lifecycle(
            "promote", over=str(dead),
            seconds=self.last_promotion["seconds"],
            fence_epoch=self.last_promotion["fence_epoch"])
        self.logger.info(
            f"standby promoted over {dead} in "
            f"{self.last_promotion['seconds']}s "
            f"(fence epoch {self.last_promotion['fence_epoch']})")

    # ================= live config reload ==================================

    def on_config_change(self, cb: Callable[[SiloConfig], Any]) -> None:
        """Subscribe to live config updates (reference: OnConfigChange
        hooks, Silo.cs:179,184,257; InsideGrainClient.cs:83)."""
        self._config_listeners.append(cb)

    def update_config(self, changes: Dict[str, Any]) -> None:
        """Apply a partial config dict (SiloConfig.from_dict shape) to the
        RUNNING silo: mutate the live dataclasses, re-push the values
        components copied at construction, notify subscribers.  Identity
        and topology fields (name/host/port/host_grains) are not
        reloadable — same as the reference."""
        import dataclasses as _dc
        if not isinstance(changes, dict):
            raise TypeError(f"config changes must be a dict, "
                            f"got {type(changes).__name__}")
        for section, value in changes.items():
            if section in ("name", "host", "port", "host_grains"):
                continue  # identity/topology: restart-only
            current = getattr(self.config, section, None)
            if _dc.is_dataclass(current):
                if not isinstance(value, dict):
                    # never replace a section object with a scalar — that
                    # would corrupt the RUNNING silo's config
                    raise TypeError(
                        f"config section {section!r} needs a dict, "
                        f"got {type(value).__name__}")
                for k, v in value.items():
                    if hasattr(current, k):
                        setattr(current, k, v)
            elif hasattr(self.config, section):
                setattr(self.config, section, value)
        # re-push values that components copied out of the config at
        # construction time (everything else reads the live dataclass)
        m = self.config.messaging
        self.runtime_client.response_timeout = m.response_timeout
        self.runtime_client.max_resend_count = m.max_resend_count
        self.dispatcher.perform_deadlock_detection = m.deadlock_detection
        self.max_forward_count = m.max_forward_count
        self.catalog.age_limit = self.config.collection.default_age_limit
        self.grain_directory.cache.max_size = self.config.directory.cache_size
        r = self.config.resilience
        self.runtime_client.backoff_enabled = r.backoff_enabled
        self.runtime_client.backoff.base = r.backoff_base
        self.runtime_client.backoff.cap = r.backoff_cap
        self.retry_budget.capacity = r.retry_budget_capacity
        self.retry_budget.fill_rate = r.retry_budget_fill
        self.retry_budget.enabled = r.backoff_enabled
        self.breakers.configure(
            enabled=r.breaker_enabled,
            failure_threshold=r.breaker_failure_threshold,
            reset_timeout=r.breaker_reset_timeout,
            half_open_probes=r.breaker_half_open_probes)
        sc = self.shed_controller
        sc.enabled = r.shed_enabled
        sc.queue_soft, sc.queue_hard = r.shed_queue_soft, r.shed_queue_hard
        sc.ttl_reference = r.shed_ttl_reference
        sc.sample_period = r.shed_sample_period
        sc.stall_level = r.shed_stall_level
        sc.stall_window = r.shed_stall_window
        self.dead_letters.resize(r.dead_letter_capacity)
        tr = self.config.tracing
        self.spans.configure(
            enabled=tr.enabled, sample_rate=tr.sample_rate,
            flight_capacity=tr.flight_recorder_capacity,
            breaker_capacity=tr.breaker_transition_capacity)
        if self.spans.timeline is not None:
            self.spans.timeline.enabled = \
                tr.enabled and tr.timeline_enabled
        mc = self.config.metrics
        if self.tensor_engine is not None:
            self.tensor_engine.metrics_config = mc
            self.tensor_engine.ledger.configure(
                enabled=mc.enabled and mc.ledger_enabled,
                n_buckets=mc.ledger_buckets)
            self.tensor_engine.attribution.configure(
                enabled=mc.enabled and mc.attribution_enabled,
                top_k=mc.attribution_top_k,
                cms_depth=mc.attribution_cms_depth,
                cms_width=mc.attribution_cms_width)
            # device cost plane: the profiler reads the SAME ProfilerConfig
            # dataclass object update_config just mutated — configure()
            # only refreshes derived state (bucket-array layout)
            self.tensor_engine.profiler.configure()
        # collection knobs: the engine reads pause budget/chunk/cadence
        # off the live dataclass every tick, but each arena copied the
        # compaction threshold at creation — re-push it
        if self.tensor_engine is not None:
            thr = self.config.tensor.compact_fragmentation_threshold
            for arena in self.tensor_engine.arenas.values():
                arena.compact_fragmentation = thr
        if self.watchdog is not None and self.config.watchdog_period > 0:
            self.watchdog.period = self.config.watchdog_period
        if self.load_publisher is not None \
                and self.config.load_publish_period > 0:
            self.load_publisher.publish_period = \
                self.config.load_publish_period
        if self.cache_maintainer is not None \
                and self.config.directory_cache_maintenance_period > 0:
            self.cache_maintainer.period = \
                self.config.directory_cache_maintenance_period
        for cb in self._config_listeners:
            try:
                res = cb(self.config)
                if asyncio.iscoroutine(res):
                    # async listeners run as tasks (update_config is sync
                    # — same convenience on_stop gives its callbacks)
                    asyncio.get_running_loop().create_task(res)
            except Exception:  # noqa: BLE001 — one bad listener must not
                # starve the rest or mislabel an APPLIED reload as rejected
                self.logger.warn("config-change listener failed", code=2803)

    async def _stats_report_loop(self) -> None:
        """Periodic metrics publication (reference: LogStatistics.cs:33
        periodic dump driving the table/SQL publishers)."""
        try:
            while True:
                await asyncio.sleep(self.config.statistics_report_period)
                snapshot = self.metrics.snapshot()
                try:
                    self.publish_data_plane_telemetry()
                except Exception:  # noqa: BLE001 — one bad metrics
                    # collection must not silently kill the statistics
                    # loop for the silo's remaining life (same hardening
                    # as the load-publisher loop)
                    self.logger.warn("data-plane telemetry publish "
                                     "failed", code=2804)
                for pub in self.statistics_publishers.values():
                    try:
                        await pub.report(self.name, snapshot)
                    except Exception:  # noqa: BLE001 — keep reporting
                        self.logger.warn("statistics publisher failed",
                                         code=2801)
        except asyncio.CancelledError:
            pass

    # ================= resilience plane ====================================

    def _pending_request_depth(self) -> int:
        """Silo-wide pending-turn count (sum of activation mailbox
        depths) — the shed controller's queue-depth signal.  Sampled
        (memoized) by the controller, not per message.  The batched-RPC
        ingress ring is deliberately NOT counted: it drains within one
        loop iteration (a transient buffer, not standing backlog) and
        anything that can't start a turn lands in a mailbox right here
        — sustained pressure is mailbox depth, same as before the
        batched plane."""
        return sum(len(a.waiting)
                   for a in self.catalog.directory.by_activation.values())

    def _on_dead_letter(self, entry: Dict[str, Any]) -> None:
        """DeadLetterRing fan-out → an always-on drop span, so the flight
        recorder can correlate every terminal drop with the hops of the
        request it killed (entries carry the trace id)."""
        self.spans.drop(entry["reason"], detail=entry.get("detail", ""),
                        trace_id=entry.get("trace_id"),
                        method=entry.get("method", ""),
                        target=entry.get("target", ""))

    def _on_breaker_transition(self, target, old: str, new: str,
                               reason: str) -> None:
        self.logger.warn(
            f"circuit breaker {self.address}->{target}: {old} -> {new} "
            f"({reason})", code=2910)
        self.spans.note_breaker(target, old, new, reason)
        from orleans_tpu import telemetry
        if telemetry.default_manager.consumers:
            telemetry.default_manager.track_event(
                "breaker.transition",
                properties={"silo": self.name, "target": str(target),
                            "from": old, "to": new, "reason": reason})

    def snapshot(self) -> Dict[str, Any]:
        """The silo's resilience/containment snapshot: shed level +
        ``degraded`` flag, breaker states, retry budget, dead-letter
        accounting.  (``get_debug_dump`` embeds this; chaos invariants
        and the degraded bench tier read it.)"""
        out = {
            "degraded": self.shed_controller.degraded,
            "shed": self.shed_controller.snapshot(),
            "breakers": self.breakers.snapshot(),
            "retry_budget": self.retry_budget.snapshot(),
            "dead_letters": self.dead_letters.snapshot(),
            "tracing": self.spans.snapshot(),
        }
        # unified metrics plane: ONE registry collection, reused by the
        # cluster merge over every peer's piggybacked snapshot
        own_metrics = self.collect_metrics()
        out["metrics"] = own_metrics
        out["cluster_metrics"] = self.cluster_metrics(own_metrics)
        if out["degraded"]:
            # a degraded silo self-reports its crash evidence: the
            # correlated spans + dead letters + breaker transitions the
            # operator needs to attribute the degradation
            out["flight_recorder"] = self.flight_dump("snapshot degraded")
        return out

    def flight_dump(self, reason: str = "") -> Dict[str, Any]:
        """The flight-recorder evidence bundle: recent spans grouped by
        trace, joined with this silo's dead letters (trace-tagged) and
        recent breaker transitions.  Chaos invariant failures and
        degraded snapshots trigger it; callable any time."""
        slices = list(self.tensor_engine.collector.last_slices) \
            if self.tensor_engine is not None else None
        captures = list(self.tensor_engine.profiler.capture_events) \
            if self.tensor_engine is not None else None
        return self.spans.flight.dump(
            reason=reason,
            dead_letters=list(self.dead_letters.entries),
            breaker_transitions=list(self.spans.breaker_transitions),
            collection_slices=slices,
            profile_captures=captures)

    def incident_bundle(self, reason: str) -> Dict[str, Any]:
        """The unified incident evidence bundle: the flight-recorder
        tail (spans correlated with dead letters + breaker
        transitions), the recent compile-event ring, the dead-letter
        tail, and the timeline tail around the trip.  Every trigger —
        a chaos invariant violation, a ``FencedError`` kill, a
        watchdog stall or failed health check, an SLO burn breach —
        dumps through here so the evidence always has one shape.  The
        newest bundles are retained on ``self.incidents`` (bounded);
        the trip itself lands on the timeline as a lifecycle mark so
        the merged cluster view shows WHEN each silo tripped."""
        import time as _time
        eng = self.tensor_engine
        tl = self.spans.timeline
        bundle = {
            "reason": reason,
            "silo": self.name,
            "at": round(_time.monotonic(), 6),
            "flight_recorder": self.flight_dump(reason),
            "compile_events": (list(eng.compile_tracker.events)[-16:]
                               if eng is not None else []),
            "dead_letters": list(self.dead_letters.entries)[-32:],
            "timeline_tail": tl.tail() if tl is not None else [],
        }
        self.incidents.append(bundle)
        if tl is not None:
            tl.lifecycle("incident", reason=reason)
        self.logger.warn(f"incident bundle dumped: {reason}", code=3003)
        return bundle

    def _fenced_kill(self) -> None:
        """Promotion-fence trip: dump the incident evidence (the fence
        epoch race IS the incident), then fast-kill — this silo must
        never acknowledge another write."""
        try:
            self.incident_bundle(
                "fenced: a promoted standby owns this silo's store")
        finally:
            self.kill()

    def capture_profile(self, ticks: int = 8,
                        reason: str = "management") -> Dict[str, Any]:
        """Explicit deep-capture entry point (the management surface —
        SiloControl.capture_profile fans in here): start a jax.profiler
        trace over the next ``ticks`` engine ticks.  Returns the capture
        event record (trace directory path, or ``error``); the same
        record rides every subsequent flight-recorder dump."""
        if self.tensor_engine is None:
            return {"error": "no tensor engine on this silo"}
        return self.tensor_engine.profiler.capture(ticks, reason=reason)

    def collect_metrics(self, mirror: bool = False,
                        force_ledger: bool = False) -> Dict[str, Any]:
        """Populate this silo's ``MetricsRegistry`` from every live
        component — dead letters, overload containment, collection,
        router slab counters, transport links, engine throughput, and
        the on-device latency ledger — and return its mergeable snapshot
        (orleans_tpu/metrics.py).  The load publisher piggybacks this on
        its broadcast; the dashboard merges them cluster-wide.  Every
        emitted name is declared in the metrics CATALOG — an undeclared
        name raises here, which is the contract the lint test pins.

        ``mirror=True`` additionally fans the same (name, value) pairs
        out to the process TelemetryManager's metric consumers — the
        legacy ad-hoc surface, preserved for existing sinks/tests.

        Runs as the ``orleans.metrics.collect`` stage: the ledger and
        attribution reads it triggers block the loop thread."""
        if not self.config.metrics.enabled:
            return {}
        from orleans_tpu.tensor.profiler import Stage
        with Stage("orleans.metrics.collect"):
            return self._collect_metrics(mirror, force_ledger)

    def _collect_metrics(self, mirror: bool,
                         force_ledger: bool) -> Dict[str, Any]:
        from orleans_tpu import telemetry
        reg = self.metrics_registry
        mgr = telemetry.default_manager
        fan = mirror and bool(mgr.consumers)

        def emit(values: Dict[str, Any],
                 labels: Optional[Dict[str, Any]], prefix: str) -> None:
            for k, v in values.items():
                reg.apply(prefix + k, float(v), labels)
            if fan:
                props = {"silo": self.name, **(labels or {})}
                mgr.track_metrics(values, props, prefix=prefix)

        dl = self.dead_letters.snapshot()
        emit({"total": dl["total"], **dl["by_reason"]}, None,
             "dead_letter.")
        emit({"level": self.shed_controller.level,
              "shed_count": self.shed_controller.shed_count,
              "breaker_fast_fails": self.breakers.fast_fails,
              "retries_denied": self.retry_budget.denied},
             None, "overload.")
        emit({"requests_sent": self.metrics.requests_sent,
              "requests_resent": self.metrics.requests_resent,
              "turns_executed": self.metrics.turns_executed},
             None, "host.")
        # batched host RPC plane: hits/fallbacks/expiry counters plus
        # the interval-mean window shape gauges (collect_interval is
        # the mutating read this collector alone owns)
        rs = self.rpc.snapshot()
        ri = self.rpc.collect_interval()
        emit({"fastpath_hits": rs["fastpath_hits"],
              "fastpath_fallbacks": rs["fastpath_fallbacks"],
              "expired": rs["expired"],
              "windows": rs["windows"]}, None, "rpc.")
        reg.gauge("rpc.ingress_batch_size").set(ri["ingress_batch_size"])
        reg.gauge("rpc.coalesce_wait_s").set(ri["coalesce_wait_s"])
        if fan:
            mgr.track_metric("rpc.ingress_batch_size",
                             ri["ingress_batch_size"], {"silo": self.name})
            mgr.track_metric("rpc.coalesce_wait_s",
                             ri["coalesce_wait_s"], {"silo": self.name})
        # batched silo→silo fabric: frame/member counters plus the
        # interval-mean frame shape gauge
        fs = self.rpc_fabric.snapshot()
        fi = self.rpc_fabric.collect_interval()
        emit({"fabric_frames_sent": fs["frames_sent"],
              "fabric_frames_received": fs["frames_received"],
              "fabric_frames_rejected": fs["frames_rejected"],
              "fabric_calls_sent": fs["calls_sent"],
              "fabric_calls_received": fs["calls_received"],
              "fabric_results_sent": fs["results_sent"],
              "fabric_results_received": fs["results_received"],
              "fabric_fallbacks": fs["fallbacks"],
              "fabric_bounced": fs["bounced"],
              "fabric_vector_batches": fs["vector_batches"]},
             None, "rpc.")
        reg.gauge("rpc.fabric_egress_batch").set(fi["egress_batch"])
        if fan:
            mgr.track_metric("rpc.fabric_egress_batch",
                             fi["egress_batch"], {"silo": self.name})
        # per-message forwarding: total hops plus the deepest chain seen
        # this interval (the gauge resets here — this collector owns it)
        emit({"forwarded": self.metrics.messages_forwarded},
             None, "dispatch.")
        reg.gauge("dispatch.forward_depth").set(
            float(self.dispatcher.forward_depth_max))
        if fan:
            mgr.track_metric("dispatch.forward_depth",
                             float(self.dispatcher.forward_depth_max),
                             {"silo": self.name})
        self.dispatcher.forward_depth_max = 0
        # tracing/timeline plane: span commit volume, sampled traces,
        # the timeline backlog, and the worst estimated peer clock
        # offset.  The offset gauge keeps the -1 no-data sentinel from
        # worst_clock_offset_s(): an unprobed silo must read "no
        # estimate", never "perfectly synced".
        sp = self.spans.snapshot()
        emit({"spans_started": sp["started"],
              "spans_committed": sp["recorded"],
              "sampled_traces": sp["sampled_traces"],
              "drop_spans": sp["drop_spans"]}, None, "trace.")
        tls = sp["timeline"]
        if tls is not None:
            reg.gauge("trace.timeline_backlog").set(float(tls["backlog"]))
            reg.counter("trace.timeline_dropped").set_total(tls["dropped"])
            reg.gauge("trace.worst_clock_offset_s").set(
                tls["worst_clock_offset_s"])
        # host turn latency: mirror the SiloMetrics ns-bucket histogram
        # into the registry's log2 layout (same octave scheme, base 1ns)
        tl = self.metrics.turn_latency
        if tl.count:
            hist = reg.histogram("host.turn_latency_s", base=1e-9,
                                 n_buckets=len(tl.buckets) + 1)
            hist.set_counts([0] + list(tl.buckets), tl.total)
        if self.vector_router is not None \
                and hasattr(self.vector_router, "snapshot"):
            emit(self.vector_router.snapshot(), None, "router.")
        snap = getattr(self._bound_transport, "snapshot", None)
        if snap is not None:
            for link, stats in snap().get("links", {}).items():
                emit(stats, {"link": link}, "transport.link.")
        eng = self.tensor_engine
        if eng is not None:
            col = eng.collector
            emit({"pause_p99_s": col.pause_p99_s(),
                  "max_pause_s": col.max_pause_s,
                  "rows_evicted": col.rows_evicted,
                  "sweeps_completed": col.sweeps_completed,
                  "write_back_failures": col.write_back_failures},
                 None, "collect.")
            for name, arena in eng.arenas.items():
                reg.gauge("arena.fragmentation",
                          {"arena": name}).set(arena.fragmentation())
                if fan:
                    mgr.track_metric("arena.fragmentation",
                                     arena.fragmentation(),
                                     {"silo": self.name, "arena": name})
                if arena.n_shards > 1:
                    # per-shard balance of the mesh-sharded arena (the
                    # exchange's load-balance health signal)
                    for shard, rows in \
                            enumerate(arena.shard_occupancy().tolist()):
                        reg.gauge("arena.shard_occupancy",
                                  {"arena": name,
                                   "shard": str(shard)}).set(rows)
            if eng.exchange is not None:
                xs = eng.exchange.snapshot()
                emit({"cross_shard_msgs": xs["cross_shard_msgs"],
                      "delivered_msgs": xs["delivered_msgs"],
                      "exchange_dropped": xs["dropped_msgs"],
                      "exchanges": xs["exchanges_run"],
                      "exchange_s": xs["exchange_seconds"],
                      "exchange_overlap_s": xs["overlap_seconds"]},
                     None, "route.")
                reg.gauge("route.exchange_util").set(
                    xs["bucket_utilization"])
                if fan:
                    mgr.track_metric("route.exchange_util",
                                     xs["bucket_utilization"],
                                     {"silo": self.name})
                # per-destination occupancy-sized caps (the sizing
                # signal the exchange plans from) + their steady-state
                # fill (proof each lane is sized to ITS traffic)
                for shard, cap in eng.exchange.cap_gauges().items():
                    reg.gauge("route.exchange_cap",
                              {"shard": str(shard)}).set(cap)
                for shard, util in \
                        eng.exchange.cap_util_gauges().items():
                    reg.gauge("route.exchange_cap_util",
                              {"shard": str(shard)}).set(util)
            for (src_t, src_m), route in eng._stream_routes.items():
                ss = route.snapshot()
                emit({"published_events": ss["published_events"],
                      "delivered_events": ss["delivered_events"],
                      "subscriptions": ss["edges"],
                      "cold_subscribers": ss["cold_subscribers"],
                      "rebuilds": ss["rebuilds"],
                      "retired_edges": ss["retired_edges"],
                      "dropped_lanes": ss["dropped_lanes"],
                      "redeliveries": ss["redeliveries"]},
                     {"route": f"{src_t}.{src_m}"}, "stream.")
            for (src_t, src_m), (fanout, _, _) in eng._fanouts.items():
                emit(fanout.snapshot(), {"route": f"{src_t}.{src_m}"},
                     "fanout.")
            # device timers plane: wheel population + harvest health
            # (the dashboard's timers row reads these)
            tm = eng.timers.snapshot()
            emit({"fired": tm["fired"],
                  "re_armed": tm["re_armed"],
                  "cancelled": tm["cancelled"],
                  "exported": tm["exported"],
                  "adopted": tm["adopted"],
                  "harvest_seconds": tm["harvest_seconds"]},
                 None, "timer.")
            reg.gauge("timer.armed").set(float(tm["armed"]))
            reg.gauge("timer.mean_harvest_width").set(
                float(tm["mean_harvest_width"]))
            reg.gauge("timer.worst_lateness_ticks").set(
                float(tm["worst_lateness_ticks"]))
            ck = eng.checkpointer
            if ck.enabled:
                # durable state plane: checkpoint / journal health +
                # the committed-recovery-point age (the live
                # loss-window gauge the dashboard's durability row
                # renders)
                emit({"full_snapshots": ck.full_snapshots,
                      "delta_snapshots": ck.delta_snapshots,
                      "rows_written": ck.rows_written,
                      "bytes_written": ck.bytes_written,
                      "restored_rows": ck.restored_rows},
                     None, "ckpt.")
                reg.gauge("ckpt.age_ticks").set(float(ck.age_ticks()))
                reg.gauge("ckpt.pause_p99_s").set(ck.pause_p99_s())
                reg.gauge("ckpt.max_pause_s").set(ck.max_pause_s)
                reg.gauge("ckpt.dirty_rows").set(
                    float(ck.last_dirty_rows))
                reg.gauge("ckpt.restore_s").set(ck.last_restore_s)
                js = ck.journal.snapshot()
                emit({"appended_lanes": sum(
                          s["appended_lanes"]
                          for s in js["sites"].values()),
                      "segments": js["segments_committed"],
                      "ring_overflows": js["ring_overflows"],
                      "replayed_lanes": js["replayed_lanes"],
                      "flush_s": js["flush_seconds"]},
                     None, "journal.")
                reg.gauge("journal.pending_lanes").set(
                    float(js["pending_lanes"]))
            # warm standby & recovery plane: the standby-lag gauge uses
            # the same -1 sentinel discipline as ckpt.age_ticks — a
            # silo that is not a standby reports -1, and the dashboard
            # cluster row lets the sentinel dominate (no standby
            # anywhere = no failover cover, surfaced, not averaged
            # away)
            reg.gauge("ckpt.standby_lag_ticks").set(
                float(self.standby.lag_ticks())
                if self.standby is not None else -1.0)
            if self.standby is not None:
                sb = self.standby.snapshot()
                reg.counter("ckpt.standby_polls").set_total(sb["polls"])
                reg.counter("ckpt.standby_adopted_rows").set_total(
                    sb["adopted_rows"])
                reg.gauge("ckpt.standby_staged_segments").set(
                    float(sb["staged_segments"]))
            emit({"promotions": ck.promotions,
                  "fused_windows": ck.replay_fused_windows,
                  "fused_lanes": ck.replay_fused_lanes},
                 None, "recovery.")
            reg.gauge("recovery.last_rto_s").set(ck.last_rto_s)
            emit({"messages_processed": eng.messages_processed,
                  "ticks": eng.ticks_run,
                  "compiles": eng.compile_count(),
                  "tick_seconds": eng.tick_seconds,
                  # continuous pipelined ticking (engine.TickPipeline):
                  # in-flight window, overlap credit, donation health
                  "inflight_ticks": eng.pipeline.inflight(),
                  "overlap_s": eng.pipeline.overlap_seconds,
                  "donation_fallbacks": eng.donation_fallbacks,
                  "latency_budget_s": eng.config.target_tick_latency},
                 None, "engine.")
            reg.gauge("tensor.shards").set(float(eng.n_shards))
            # compile-churn attribution: cause-coded counters replace
            # the bare compiles int as the actionable churn signal
            ct = eng.compile_tracker
            for cause, n in ct.by_cause.items():
                if n:
                    reg.counter("compile.events",
                                {"cause": cause}).set_total(n)
            reg.counter("compile.lowering_s").set_total(
                ct.lowering_seconds)
            # tick-phase profiler: mirror the cumulative per-phase log2
            # histograms (same set_counts discipline as the ledger)
            prof = eng.profiler
            if prof.enabled and prof.ticks_observed:
                for phase, counts in prof.phase_counts.items():
                    reg.histogram("engine.phase_s", {"phase": phase},
                                  base=prof.hist_base,
                                  n_buckets=len(counts)
                                  ).set_counts(counts,
                                               prof.phase_seconds[phase])
            # memory ledger: HBM by owner + headroom; the headroom gauge
            # also feeds the shed controller's memory floor
            mem = eng.memledger.snapshot()
            reg.gauge("memory.self_bytes").set(mem["total_self_bytes"])
            reg.gauge("memory.peak_bytes").set(mem["peak_self_bytes"])
            groups: Dict[str, float] = {}
            for owner, nbytes in mem["owners"].items():
                group = ".".join(owner.split(".")[:2]) \
                    if owner.startswith("arena.") else owner
                groups[group] = groups.get(group, 0.0) + nbytes
            for group, nbytes in groups.items():
                reg.gauge("memory.owner_bytes", {"owner": group}).set(nbytes)
            dev_mem = mem["device"]
            if dev_mem is not None:
                if "bytes_in_use" in dev_mem:
                    reg.gauge("memory.device_bytes_in_use").set(
                        dev_mem["bytes_in_use"])
                if "bytes_limit" in dev_mem:
                    reg.gauge("memory.device_bytes_limit").set(
                        dev_mem["bytes_limit"])
            pc = self.config.profiler
            self.shed_controller.note_memory_headroom(
                mem["headroom"], low_watermark=pc.memory_low_watermark,
                floor_level=pc.memory_shed_level)
            if mem["headroom"] is not None:
                reg.gauge("memory.headroom").set(mem["headroom"])
            # the on-device latency ledger: the bucket-count fetch is
            # ONE small d2h transfer, gated by the publish cadence so a
            # hot snapshot() loop cannot turn it into per-tick traffic.
            # The attribution plane and the latency-SLO judgement share
            # the same cadence gate (their d2h reads ride it too).
            due = force_ledger or (
                eng.tick_number - self._ledger_publish_tick
                >= self.config.metrics.publish_interval_ticks)
            if due:
                self._ledger_publish_tick = eng.tick_number
            led = eng.ledger
            if led.enabled:
                for method, h in (led.snapshot() if due else {}).items():
                    reg.histogram("engine.latency_ticks",
                                  {"method": method}, base=1.0,
                                  n_buckets=led.n_buckets
                                  ).set_counts(h["counts"])
            # closed-loop rebalance: the controller's decision counters
            # + the engine's migration totals (any source — controller,
            # ring-change handoff, drain)
            if self.rebalancer is not None:
                rb = self.rebalancer.snapshot()
                emit({"intervals": rb["intervals"],
                      "moves": rb["moves_applied"],
                      "grains_moved": rb["grains_moved"],
                      "cross_silo_grains": rb["cross_silo_grains"]},
                     None, "rebalance.")
                for reason in ("idle", "below_trigger", "hysteresis",
                               "cooldown", "no_candidates"):
                    n = rb[f"skipped_{reason}"]
                    if n:
                        reg.counter("rebalance.skipped",
                                    {"reason": reason}).set_total(n)
                reg.gauge("rebalance.trigger_share").set(
                    rb["last_trigger_share"])
                reg.gauge("rebalance.move_pause_s").set(
                    rb["max_move_pause_s"])
                reg.counter("rebalance.migrations").set_total(
                    eng.migrations)
                reg.counter("rebalance.migrated_grains").set_total(
                    eng.grains_migrated)
                # hot-grain replication: the second actuator's counters
                reg.counter("rebalance.replicated").set_total(
                    eng.grains_replicated)
                reg.counter("rebalance.demoted").set_total(
                    eng.replica_demotions)
                reg.counter("rebalance.replica_folds").set_total(
                    sum(a.replica_folds for a in eng.arenas.values()))
                reg.counter("rebalance.hot_grain_blocked").set_total(
                    rb["hot_grain_blocked"])
            att = eng.attribution
            if due:
                if att.enabled:
                    self._publish_attribution(reg, att.snapshot())
                    # the snapshot above is cached, so flattening the
                    # HotSet here is free — and the broadcast path can
                    # serve this copy on the same cadence
                    self._hot_set_cache = att.hot_set()
                elif self._hot_set_cache:
                    # attribution live-disabled since the last publish:
                    # retract the published rows and the broadcast
                    # cache — a stale HotSet/gauge row would keep
                    # feeding the rebalancer and dashboard dead data
                    for name in self._ATTRIBUTION_GAUGE_FAMILIES:
                        reg.drop_gauges(name)
                    self._hot_set_cache = []
                self._publish_slo(reg, eng)
        # timeline load context: one interval's counter deltas appended
        # to the per-silo timeline log — the lane's "what was the silo
        # doing" strip between spans
        tl_rec = self.spans.timeline
        if tl_rec is not None and tl_rec.enabled:
            totals = {
                "turns_executed": float(self.metrics.turns_executed),
                "requests_sent": float(self.metrics.requests_sent),
                "rpc_fastpath_hits": float(rs["fastpath_hits"]),
                "dead_letters": float(dl["total"]),
                "spans_committed": float(self.spans.recorded),
            }
            if eng is not None:
                totals["engine_ticks"] = float(eng.ticks_run)
                totals["engine_messages"] = float(eng.messages_processed)
            last, self._timeline_totals = self._timeline_totals, totals
            tl_rec.metrics_delta(
                {k: v - last.get(k, 0.0) for k, v in totals.items()
                 if v != last.get(k, 0.0)})
        return reg.snapshot()

    #: every attribution gauge family whose label VALUES churn between
    #: publishes — dropped before each re-publish, and retracted
    #: wholesale when the plane is live-disabled
    _ATTRIBUTION_GAUGE_FAMILIES = (
        "hot.grain_msgs", "hot.grain_share", "hot.topk_share",
        "hot.confidence", "skew.max_shard_share", "skew.gini",
        "skew.p99_to_mean")

    def _publish_attribution(self, reg, snap: Dict[str, Any]) -> None:
        """Mirror the workload-attribution snapshot into the registry's
        hot.*/skew.* rows (tensor/attribution.py): HotSet grains keyed
        by (arena, key) gauge labels so the offline dashboard merge and
        the load-publisher broadcast carry them without a side channel.
        Every re-published family is dropped first: the label values
        churn (grains enter and leave the hot set, arenas come and go),
        and a gauge left behind would sit stale in every later snapshot
        while the registry's cardinality grew without bound."""
        for name in self._ATTRIBUTION_GAUGE_FAMILIES:
            reg.drop_gauges(name)
        tracked = 0
        for arena_name, a in snap["arenas"].items():
            tracked += a["total_msgs"]
            labels = {"arena": arena_name}
            sk = a["skew"]
            reg.gauge("skew.max_shard_share",
                      labels).set(sk["max_shard_share"])
            reg.gauge("skew.gini", labels).set(sk["gini"])
            reg.gauge("skew.p99_to_mean", labels).set(sk["p99_to_mean"])
            reg.gauge("hot.topk_share", labels).set(a["topk_share"])
            reg.gauge("hot.confidence",
                      labels).set(snap["sketch"]["confidence"])
            for h in a["hot"]:
                hl = {"arena": arena_name, "key": str(h["key"])}
                reg.gauge("hot.grain_msgs", hl).set(h["msgs"])
                reg.gauge("hot.grain_share", hl).set(h["share"])
        reg.counter("hot.tracked_msgs").set_total(tracked)
        for method, msgs in snap["methods"].items():
            reg.counter("hot.method_msgs",
                        {"method": method}).set_total(msgs)

    def _publish_slo(self, reg, eng) -> None:
        """The cluster SLO rollup's per-silo half: judge the device
        ledger's latency distribution against the live budget and the
        drop counters against the offered load, as burn-rate gauges
        (slo.* catalog rows).  Counters are cluster-mergeable, so the
        dashboard recomputes the CLUSTER burn from summed counters and
        names the silo responsible from the per-source gauges."""
        from orleans_tpu.metrics import bucket_bounds
        mc = self.config.metrics
        budget = eng.config.target_tick_latency
        window = over = 0
        if budget > 0 and eng.ledger.enabled and eng.ticks_run:
            spt = eng.tick_seconds / eng.ticks_run
            counts = eng.ledger.fetch_counts()
            window = int(counts.sum())
            if spt > 0:
                bounds = bucket_bounds(1.0, eng.ledger.n_buckets)
                # conservative: only buckets whose LOWER bound already
                # exceeds the budget count as surely-over
                over_buckets = [k for k, (lo, _hi) in enumerate(bounds)
                                if lo * spt > budget]
                over = int(counts[:, over_buckets].sum()) \
                    if over_buckets else 0
        reg.counter("slo.latency_window_msgs").set_total(window)
        reg.counter("slo.latency_over_budget").set_total(over)
        lat_burn = (over / window / mc.slo_latency_error_budget) \
            if window and mc.slo_latency_error_budget > 0 else 0.0
        reg.gauge("slo.latency_burn_rate").set(lat_burn)
        reg.gauge("slo.latency_error_budget").set(
            mc.slo_latency_error_budget)
        dropped = self.dead_letters.total + self.shed_controller.shed_count
        attempted = dropped + eng.messages_processed \
            + self.metrics.requests_sent
        reg.counter("slo.dropped_msgs").set_total(dropped)
        reg.counter("slo.attempted_msgs").set_total(attempted)
        drop_burn = (dropped / attempted / mc.slo_drop_error_budget) \
            if attempted and mc.slo_drop_error_budget > 0 else 0.0
        reg.gauge("slo.drop_burn_rate").set(drop_burn)
        reg.gauge("slo.drop_error_budget").set(mc.slo_drop_error_budget)
        healthy = lat_burn <= 1.0 and drop_burn <= 1.0
        reg.gauge("slo.healthy").set(1.0 if healthy else 0.0)
        # edge-triggered incident dump: the FIRST publish that finds a
        # burn rate over budget captures the evidence around the breach
        # (re-dumping every interval would flood the bounded rings)
        if not healthy and self._slo_was_healthy:
            self.incident_bundle(
                f"slo burn breach: latency_burn={lat_burn:.3f} "
                f"drop_burn={drop_burn:.3f}")
        self._slo_was_healthy = healthy

    def hot_set(self, refresh: bool = False) -> List[Dict[str, Any]]:
        """The silo's HotSet — hot grains with estimated message share
        and sketch confidence (tensor/attribution.py contract).  The
        load publisher broadcasts it with the runtime statistics; the
        rebalance plane (ROADMAP item 4) consumes it unchanged.

        Serves the copy cached by the cadence-gated attribution publish
        (``collect_metrics``): the attribution snapshot cache keys on
        the fold count, which moves every tick under traffic, so an
        on-demand read per publisher broadcast would be an ungated
        blocking device fetch — exactly the per-interval sync point the
        ledger's cadence gate exists to prevent.  ``refresh=True`` (or
        a never-published silo) computes live — the interactive /
        diagnostic read, an explicit device fetch like
        ``ledger.snapshot()``.  A live-disabled plane reports empty
        immediately — the cadence-gated retraction must not gate the
        broadcast on serving one more stale copy."""
        eng = self.tensor_engine
        if eng is None or not self.config.metrics.enabled \
                or not eng.attribution.enabled:
            return []
        if refresh or self._hot_set_cache is None:
            self._hot_set_cache = eng.attribution.hot_set()
        return self._hot_set_cache

    def cluster_metrics(self, own: Optional[Dict[str, Any]] = None
                        ) -> Dict[str, Any]:
        """The merged cluster view: this silo's registry + the freshest
        snapshot every peer piggybacked on its load broadcast (counters
        and histogram buckets sum; gauges stay per-source).  ``own``
        reuses an already-collected snapshot (snapshot() collects once
        and merges from it)."""
        from orleans_tpu.metrics import merge_snapshots
        snaps = [own if own is not None else self.collect_metrics()]
        if self.load_publisher is not None:
            for addr, st in self.load_publisher.periodic_stats.items():
                if addr != self.address \
                        and getattr(st, "metrics", None):
                    snaps.append(st.metrics)
        return merge_snapshots(snaps)

    def publish_data_plane_telemetry(self) -> None:
        """Refresh the metrics registry AND mirror the data-plane
        counters to the process telemetry manager (the legacy fan-out
        surface; sinks keep seeing the same names/properties)."""
        self.collect_metrics(mirror=True)

    # ================= membership view =====================================

    def active_silos(self) -> List[SiloAddress]:
        if self.membership_oracle is not None:
            return self.membership_oracle.active_silos()
        return self.ring.members

    def hosting_silos(self) -> List[SiloAddress]:
        """Placement-eligible members (excludes non-hosting observers
        like the admin CLI; see SiloConfig.host_grains)."""
        if self.membership_oracle is not None:
            return self.membership_oracle.hosting_silos()
        return self.ring.members

    def is_silo_alive(self, addr: SiloAddress) -> bool:
        if self.membership_oracle is not None:
            return self.membership_oracle.is_alive(addr)
        return addr in self.ring.members

    def on_silo_dead(self, addr: SiloAddress) -> None:
        """Fan-out of a death notification (reference: Silo.cs:364-376
        status-change listeners)."""
        self.ring.remove_silo(addr)
        if self.standby is not None and not self.standby.promoted \
                and (not self._standby_primary
                     or self._standby_primary in (addr.host, str(addr))):
            # the primary we tail was declared DEAD: promote — fence
            # its store, replay the staged tail, serve its ring range
            # (the ring removal above already re-homed it onto us)
            asyncio.ensure_future(self._promote_standby(addr))
        self.grain_directory.on_silo_dead(addr)
        # fail the fabric's still-ringed sends to the corpse FIRST —
        # their requests become TRANSIENT rejections that re-address via
        # the (just-healed) ring, no caller waits out its deadline
        self.rpc_fabric.fail_destination(addr, "silo declared dead")
        self.runtime_client.break_outstanding_messages_to_dead_silo(addr)
        # a dead silo's breaker is moot (its traffic re-addresses; a
        # replacement incarnation is a different SiloAddress)
        self.breakers.forget(addr)

    def _on_ring_changed(self) -> None:
        if self.status != SiloStatus.ACTIVE:
            return
        self.spans.timeline.lifecycle(
            "ring-change", live=len(self.active_silos()))
        # drop transport sender queues for dead endpoints (queued requests
        # bounce as transient rejections; reference: SiloDeadOracle)
        prune = getattr(self._bound_transport, "prune_dead", None)
        if prune is not None:
            prune(self.active_silos())
        self.rpc_fabric.prune_dead(set(self.active_silos()))
        if self.load_publisher is not None:
            live = set(self.active_silos())
            for s in list(self.load_publisher.periodic_stats):
                if s not in live:
                    self.load_publisher.forget(s)
        self.grain_directory.schedule_heal()
        if self.vector_router is not None:
            self.vector_router.on_ring_changed()
        gateway = self.system_targets.get("gateway")
        if gateway is not None and gateway._clients:
            asyncio.get_running_loop().create_task(
                gateway.reregister_routes())

    # ================= system targets ======================================

    def register_system_target(self, name: str, instance: Any) -> None:
        self.system_targets[name] = instance

    async def system_rpc(self, target_silo: SiloAddress, target_name: str,
                         method: str, args: tuple,
                         timeout: Optional[float] = None) -> Any:
        """Invoke a system target on any silo
        (reference: system-target GrainReferences, e.g.
        RemoteGrainDirectory calls from LocalGrainDirectory)."""
        if target_silo == self.address:
            st = self.system_targets[target_name]
            return await getattr(st, method)(*args)
        loop = asyncio.get_running_loop()
        msg = Message(
            category=Category.SYSTEM,
            direction=Direction.REQUEST,
            sending_silo=self.address,
            sending_grain=self.client_grain_id,
            target_silo=target_silo,
            target_grain=GrainId.system_target(
                _SYSTEM_TARGET_CODES[target_name]),
            method_name=method,
            args=args,
        )
        future: asyncio.Future = loop.create_future()
        cb = CallbackData(future=future, message=msg)
        t = timeout if timeout is not None else self.runtime_client.response_timeout
        cb.timeout_handle = loop.call_later(
            t, self.runtime_client._on_timeout, msg.id)
        self.runtime_client.callbacks[msg.id] = cb
        self.message_center.send_message(msg)
        return await future

    def invoke_system_target(self, msg: Message) -> None:
        """Dispatcher entry for inbound system-target messages."""
        name = _CODE_TO_NAME.get(msg.target_grain.type_code)
        st = self.system_targets.get(name) if name else None

        async def run() -> None:
            try:
                if st is None:
                    raise KeyError(f"no system target {name!r} on {self.address}")
                result = await getattr(st, msg.method_name)(*msg.args)
                if msg.direction != Direction.ONE_WAY:
                    self.message_center.send_message(msg.create_response(result))
            except Exception as exc:  # noqa: BLE001
                if msg.direction != Direction.ONE_WAY:
                    self.message_center.send_message(
                        msg.create_response(exc, ResponseKind.ERROR))
                else:
                    # a one-way system call has no caller to surface the
                    # failure to — log it, or e.g. a slab whose handler
                    # raises vanishes without a trace
                    self.logger.warn(
                        f"one-way system call {name}.{msg.method_name} "
                        f"failed: {exc!r}", code=2804, exc_info=True)

        asyncio.get_running_loop().create_task(run())

    # ================= providers ===========================================

    def storage_provider(self, name: Optional[str]) -> Optional[StorageProvider]:
        if name is None:
            return self.storage_providers.get("Default")
        provider = self.storage_providers.get(name)
        if provider is None:
            raise KeyError(
                f"storage provider {name!r} not configured on silo "
                f"{self.name} (reference: StorageProviderManager lookup)")
        return provider

    def add_storage_provider(self, name: str, provider: StorageProvider) -> None:
        self.storage_providers[name] = provider

    def stream_provider(self, name: str):
        provider = self.stream_providers.get(name)
        if provider is None:
            raise KeyError(f"stream provider {name!r} not configured")
        return provider

    def add_stream_provider(self, name: str, provider) -> None:
        """Register + wire a stream provider; call before start()
        (reference: stream provider config blocks, Silo.cs:488-495)."""
        provider.init(self, name)
        self.stream_providers[name] = provider

    def attach_client(self) -> GrainFactory:
        """Bind the calling context to this silo as an in-process client
        (reference: GrainClient.Initialize for the hosted-client case).
        Returns the grain factory; subsequent grain calls in this task (and
        its children) route through this silo."""
        from orleans_tpu.core.reference import bind_runtime
        bind_runtime(self.runtime_client)
        return self.factory

    # ================= client edge =========================================

    def deliver_to_client(self, msg: Message) -> None:
        """Deliver a message addressed to a client grain-id (observer calls,
        gateway replies) — wired by the gateway (phase: client runtime)."""
        gateway = self.system_targets.get("gateway")
        if gateway is not None:
            gateway.deliver(msg)
        else:
            self.logger.warn(f"dropping client-bound message {msg}: no gateway")

    # ================= debug ===============================================

    def get_debug_dump(self) -> Dict[str, Any]:
        """(reference: Silo.GetDebugDump :1057)"""
        dump = {
            "address": str(self.address),
            "status": self.status.value,
            "activations": len(self.catalog.directory),
            "metrics": self.metrics.snapshot(),
            "ring_members": [str(s) for s in self.ring.members],
            "resilience": self.snapshot(),
        }
        if self.vector_router is not None \
                and hasattr(self.vector_router, "snapshot"):
            dump["vector_router"] = self.vector_router.snapshot()
        snap = getattr(self._bound_transport, "snapshot", None)
        if snap is not None:
            dump["transport"] = snap()
        return dump
