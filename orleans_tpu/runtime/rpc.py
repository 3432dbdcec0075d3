"""Batched host RPC plane: ingress ring, coalesced invoke windows,
pre-resolved invoke tables, and the multi-process proof harness.

Parity: the reference fronts millions of client connections through
gateway silos that forward ONE proxied message at a time onto the silo
messaging stack (reference: Gateway.cs:37 per-client proxy loop;
Dispatcher.cs:78 per-message receive; the custom binary serializer +
socket message pump of the paper).  Every data plane in this rebuild is
batched; this module batches the FRONT DOOR the same way dispatch was
batched:

* calls entering a silo (hosted client sends, TCP gateway calls-frames)
  land in an **ingress ring** instead of becoming per-call Messages;
* a **coalescer** drains the ring into (type, method) **windows** —
  the same key/args-columns shape ``Gateway.submit_batch`` already
  speaks for vector slabs — preserving per-sender FIFO across windows;
* the dispatcher executes a window through a **pre-resolved invoke
  table**: (type_code, method) → activation-turn entrypoint + bound
  per-activation methods, memoized at first sight and invalidated on
  the catalog's deactivation epoch (the host-path analog of every
  device plane's generation/eviction-epoch discipline);
* per-call reply futures resolve from the one batched completion; the
  per-message pipeline stays as the correctness net (cold/busy/remote
  activations, chaos injection, shed pressure all fall back per call
  and are counted as ``rpc.fastpath_fallbacks``).  Sampled traces RIDE
  the fastpath — the calls frame carries an optional per-lane trace
  column and the window links member traces to its batched span — so
  tracing never perturbs the path it measures.

TTL semantics are preserved per call: every coalesced call carries its
own absolute deadline (gateway frames rebase per-call remaining TTLs on
this host's clock), an expired call dead-letters with reason
``expired`` and answers an EXPIRED rejection — never a silent drop —
and a per-window watchdog enforces deadlines even while a window is
stuck in a hung user method.

``python -m orleans_tpu.runtime.rpc --serve|--drive`` is the
multi-process proof harness: real silo server processes (optionally
clustered through a table-service process — no shared memory anywhere)
and external client driver processes talking real TCP to the gateway.
The bench rpc tier and the ``@pytest.mark.rpc`` multiprocess smoke both
ride it.  It needs no ``jax.distributed`` init — the control plane is
plain sockets — so it runs wherever subprocesses and loopback TCP do.
"""

from __future__ import annotations

import asyncio
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from orleans_tpu.core.grain import MethodInfo, registry as type_registry
from orleans_tpu.ids import GrainId


class _Call:
    """One coalesced RPC call: the envelope fields the window executor
    actually needs — no Message object, no header dictionary."""

    __slots__ = ("grain_id", "method", "iface_id", "args", "future",
                 "deadline", "sender", "trace", "forward_count",
                 "wire_id", "hop")

    def __init__(self, grain_id: GrainId, method: MethodInfo,
                 iface_id: int, args: Tuple[Any, ...],
                 future: Optional[asyncio.Future],
                 deadline: Optional[float], sender: Any,
                 trace: Optional[Dict[str, Any]] = None,
                 forward_count: int = 0) -> None:
        self.grain_id = grain_id
        self.method = method
        self.iface_id = iface_id
        self.args = args
        self.future = future          # None = one-way
        self.deadline = deadline      # absolute time.monotonic() or None
        self.sender = sender          # FIFO key (client GrainId)
        self.trace = trace            # sampled trace context or None
        self.forward_count = forward_count  # hops already taken (fabric
        #                               ingress preserves the hop budget)
        self.wire_id = None           # frame correlation id once the call
        #                               ships DIRECTLY over the fabric
        self.hop = False              # True: arrived over the fabric —
        #                               a re-dispatch counts as a forward

    # gate compatibility: while a fast turn runs, the call sits in
    # ActivationData.running — may_interleave reads these flags off
    # every running item when a concurrent message asks to interleave
    @property
    def is_read_only(self) -> bool:
        return self.method.read_only

    @property
    def is_always_interleave(self) -> bool:
        return self.method.always_interleave


class _Window:
    """One coalesced (type_code, method) run of calls, executed as one
    batched completion by ``Dispatcher.invoke_window``."""

    __slots__ = ("type_code", "method", "iface_id", "calls")

    def __init__(self, type_code: int, method: MethodInfo,
                 iface_id: int) -> None:
        self.type_code = type_code
        self.method = method
        self.iface_id = iface_id
        self.calls: List[_Call] = []


class InvokeEntry:
    """Memoized (type_code, method) → turn entrypoint + arg spec.

    ``acts`` caches ``grain_id → (ActivationData, bound method)`` so a
    steady-state call is one dict hit; entries self-invalidate through
    the per-call ``state is VALID`` check and the whole cache drops when
    the catalog's deactivation epoch moves (InvokeTable.resolve)."""

    __slots__ = ("type_code", "method_name", "class_info", "func",
                 "acts", "epoch")

    def __init__(self, type_code: int, method_name: str) -> None:
        self.type_code = type_code
        self.method_name = method_name
        self.class_info = type_registry.by_type_code.get(type_code)
        # the activation-turn entrypoint (unbound); None → every call
        # falls back to the per-message path, which surfaces the
        # AttributeError/forwarding exactly like an unbatched call
        self.func = (getattr(self.class_info.cls, method_name, None)
                     if self.class_info is not None else None)
        self.acts: Dict[GrainId, Tuple[Any, Callable]] = {}
        self.epoch = -1


class InvokeTable:
    """The dispatcher's pre-resolved invoke tables (tentpole leg 3).

    Resolution happens once per (type, method) — the per-window cost is
    a dict hit, not reflection.  Invalidated on the catalog's
    deactivation count (the host path's eviction epoch): any activation
    deactivating drops the cached per-key bindings, exactly like every
    device plane's cached plans drop on an eviction-epoch bump."""

    def __init__(self, silo) -> None:
        self.silo = silo
        self._entries: Dict[Tuple[int, str], InvokeEntry] = {}
        self.resolves = 0  # cold (type, method) resolutions (telemetry)

    def resolve(self, type_code: int, method_name: str) -> InvokeEntry:
        key = (type_code, method_name)
        entry = self._entries.get(key)
        if entry is None:
            entry = InvokeEntry(type_code, method_name)
            self._entries[key] = entry
            self.resolves += 1
        epoch = self.silo.catalog.deactivations_count
        if entry.epoch != epoch:
            # eviction-epoch bump: a deactivated activation's row must
            # never serve a call from the cache (its slot — the grain
            # identity — may be re-activated as a DIFFERENT object)
            entry.acts.clear()
            entry.epoch = epoch
        return entry

    def invalidate(self) -> None:
        self._entries.clear()

    def __len__(self) -> int:
        return len(self._entries)


async def drive_started_turn(coro, yielded):
    """Finish a turn coroutine whose FIRST step ran eagerly inside an
    invoke window.  The window executes each call's first step inline;
    a method that completes without suspending (the steady-state shape)
    never allocates a task — one that awaits real IO suspends here and
    is promoted.  A started coroutine cannot be handed to ``Task``
    (``Future.__await__`` refuses resumption before its future is
    done), so this duplicates the narrow slice of ``Task.__step`` the
    promotion needs: wait for each yielded future, resume, repeat."""
    loop = asyncio.get_running_loop()
    while True:
        if yielded is not None:
            if getattr(yielded, "_asyncio_future_blocking", None) is None:
                coro.close()
                raise RuntimeError(
                    f"turn coroutine yielded a non-future {yielded!r}")
            yielded._asyncio_future_blocking = False
            if not yielded.done():
                waiter = loop.create_future()

                def _wake(_f, w=waiter) -> None:
                    if not w.done():
                        w.set_result(None)

                yielded.add_done_callback(_wake)
                await waiter
            # the coroutine fetches result()/exception itself on resume
        else:
            await asyncio.sleep(0)  # bare yield
        try:
            yielded = coro.send(None)
        except StopIteration as stop:
            return stop.value


class _WindowWatchdog:
    """Deadline enforcement for an executing window: one timer at the
    earliest unresolved deadline (re-armed as deadlines resolve), NOT a
    ``call_later`` per call — per-call timers are exactly the per-call
    host cost this plane deletes.  Fires the full expire path (dead
    letter + EXPIRED rejection) so a call stuck behind a hung user
    method still dead-letters on time."""

    __slots__ = ("_loop", "_calls", "_expire", "_handle", "_cancelled")

    def __init__(self, loop, calls: List[_Call],
                 expire: Callable[[_Call], None]) -> None:
        self._loop = loop
        self._calls = calls
        self._expire = expire
        self._handle = None
        self._cancelled = False
        self._arm()

    def _arm(self) -> None:
        if self._cancelled:
            return
        pending = [c.deadline for c in self._calls
                   if c.deadline is not None and c.future is not None
                   and not c.future.done()]
        if not pending:
            return
        self._handle = self._loop.call_later(
            max(0.0, min(pending) - time.monotonic()), self._fire)

    def _fire(self) -> None:
        now = time.monotonic()
        for c in self._calls:
            if (c.deadline is not None and now >= c.deadline
                    and c.future is not None and not c.future.done()):
                self._expire(c)
        self._arm()

    def cancel(self) -> None:
        self._cancelled = True
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None


class RpcCoalescer:
    """Tentpole leg 1: the batched gateway/hosted-client ingress.

    ``submit`` appends to the ingress ring and wakes the drain task;
    the drain groups everything pending into per-(type, method) windows
    and executes them sequentially through the dispatcher.  Calls
    submitted while a window executes batch up for the next cycle —
    coalescing deepens naturally under load, the same dynamic the
    tensor engine's queue→tick loop has.

    Ordering contract: windows execute in creation order and one at a
    time, calls within a window in arrival order, and the window
    builder never lets a sender's later call land in an EARLIER window
    than any of its previous calls — so per-sender FIFO holds across
    coalesced windows (property-tested in tests/test_rpc.py)."""

    def __init__(self, silo) -> None:
        self.silo = silo
        # the live RpcConfig object (update_config mutates it in place,
        # so holding the reference is reload-safe and saves the
        # config-attribute chain on every submit)
        self.cfg = silo.config.rpc
        self._ring: "deque[_Call]" = deque()
        self._drain_task: Optional[asyncio.Task] = None
        # cumulative counters (collect_metrics derives interval means)
        self.fastpath_hits = 0
        self.fastpath_fallbacks = 0
        self.expired = 0
        self.windows_run = 0
        self.calls_coalesced = 0
        self.wait_s_sum = 0.0      # per-drain batch-head wait samples
        self._ring_t0 = 0.0        # when the pending batch head arrived
        self._snap = (0, 0, 0.0)   # (calls, windows, wait) at last snap

    # -- ingress ------------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self.cfg.fastpath_enabled

    def accepting(self) -> bool:
        """Admission: the plane takes the call unless disabled or the
        ring is at its bound (the per-message path's mailbox/shed
        machinery is the real backpressure surface)."""
        cfg = self.cfg
        return cfg.fastpath_enabled and len(self._ring) < cfg.max_pending

    def submit(self, call: _Call) -> None:
        ring = self._ring
        if not ring:
            # wait accounting rides the batch head (the longest waiter),
            # not a clock read per call
            self._ring_t0 = time.perf_counter()
        if call.trace is not None:
            # sampled lanes stamp their own enqueue instant so the
            # window span can attribute THIS call's coalesce wait (the
            # unsampled majority still pays no clock read)
            call.trace["enq"] = time.monotonic()
        ring.append(call)
        if self._drain_task is None or self._drain_task.done():
            self._drain_task = asyncio.get_running_loop().create_task(
                self._drain())

    def pending(self) -> int:
        return len(self._ring)

    async def wait_idle(self) -> None:
        """Settle helper (tests/bench): resolve when the ring is empty
        and the current drain has finished."""
        while self._ring or (self._drain_task is not None
                             and not self._drain_task.done()):
            task = self._drain_task
            if task is not None and not task.done():
                await asyncio.shield(task)
            else:
                await asyncio.sleep(0)

    # -- drain --------------------------------------------------------------

    async def _drain(self) -> None:
        from orleans_tpu.core.context import RequestContext
        # the drain task inherits the SUBMITTER's context snapshot —
        # clear the ambient request context so nested sends made inside
        # fast turns never see the client's exported dictionary
        RequestContext.import_(None)
        silo = self.silo
        dispatcher = silo.dispatcher
        while self._ring:
            self.wait_s_sum += time.perf_counter() - self._ring_t0
            for window in self._build_windows():
                n = len(window.calls)
                self.windows_run += 1
                self.calls_coalesced += n
                # per-call accounting the submit path deferred, batched:
                # same totals as n per-message send_request calls
                silo.metrics.requests_sent += n
                silo.retry_budget.on_requests(n)
                try:
                    await dispatcher.invoke_window(window)
                except Exception as exc:  # noqa: BLE001 — a window-level
                    # fault (never a user fault; those resolve per call)
                    # must fail ITS callers now, not strand them until
                    # their deadlines, and must not stop later windows
                    silo.logger.warn(
                        f"rpc invoke window failed: {exc!r}", code=2920)
                    for call in window.calls:
                        f = call.future
                        if f is not None and not f.done():
                            f.set_exception(exc)

    def _build_windows(self) -> List[_Window]:
        """Group the pending ring into (type, method) windows preserving
        per-sender FIFO: a call may only join the open window for its
        key if that window is not EARLIER than the last window any of
        this sender's previous calls landed in; otherwise a fresh
        window opens at the end."""
        max_window = self.cfg.max_window
        ring = self._ring
        # uniform fast path: the overwhelmingly common drain is one
        # (type, method) from one edge — a single attribute-compare scan
        # instead of per-call dict bookkeeping
        if len(ring) <= max_window:
            head = ring[0]
            tc, mname = head.grain_id.type_code, head.method.name
            uniform = True
            for c in ring:
                if c.grain_id.type_code != tc or c.method.name != mname:
                    uniform = False
                    break
            if uniform:
                window = _Window(tc, head.method, head.iface_id)
                window.calls = list(ring)
                ring.clear()
                return [window]
        windows: List[_Window] = []
        open_by_key: Dict[Tuple[int, str], int] = {}
        sender_floor: Dict[Any, int] = {}
        while ring:
            call = ring.popleft()
            key = (call.grain_id.type_code, call.method.name)
            wi = open_by_key.get(key, -1)
            floor = sender_floor.get(call.sender, -1)
            if wi < 0 or wi < floor or len(windows[wi].calls) >= max_window:
                wi = len(windows)
                windows.append(_Window(call.grain_id.type_code,
                                       call.method, call.iface_id))
                open_by_key[key] = wi
            windows[wi].calls.append(call)
            sender_floor[call.sender] = wi
        return windows

    # -- telemetry ----------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """Counters + LIFETIME mean window shape.  Pure read — any
        number of consumers (bench, tests, debug dumps) may call it
        without disturbing each other; the interval-mean gauges the
        metrics plane publishes come from :meth:`collect_interval`,
        which only ``silo.collect_metrics`` consumes."""
        calls, windows = self.calls_coalesced, self.windows_run
        return {
            "fastpath_hits": self.fastpath_hits,
            "fastpath_fallbacks": self.fastpath_fallbacks,
            "expired": self.expired,
            "windows": windows,
            "calls_coalesced": calls,
            "ingress_batch_size": (calls / windows) if windows else 0.0,
            "coalesce_wait_s": (self.wait_s_sum / windows) if windows
            else 0.0,
            "pending": len(self._ring),
            "invoke_tables": len(self.silo.dispatcher.invoke_table),
        }

    def collect_interval(self) -> Dict[str, float]:
        """Interval means since the PREVIOUS collection (the
        collection-cadence semantics the rpc.* gauges document).
        Mutating read — owned by ``silo.collect_metrics`` alone."""
        calls, windows = self.calls_coalesced, self.windows_run
        wait = self.wait_s_sum
        p_calls, p_windows, p_wait = self._snap
        self._snap = (calls, windows, wait)
        dw = windows - p_windows
        return {
            "ingress_batch_size": ((calls - p_calls) / dw) if dw else 0.0,
            "coalesce_wait_s": ((wait - p_wait) / dw) if dw else 0.0,
        }


class _Result:
    """One executed fabric call's reply, ringed back to the origin as a
    bare results-section row — no RESPONSE Message object on the hot
    relay path (materialized lazily only for dead-letter/fallback)."""

    __slots__ = ("msg_id", "status", "rejection", "target", "trace",
                 "value")

    def __init__(self, msg_id: int, status: int, rejection: int,
                 target: Any, trace: Optional[Dict[str, Any]],
                 value: Any) -> None:
        self.msg_id = msg_id
        self.status = status          # FABRIC_RESULT_OK/ERROR/REJECTION
        self.rejection = rejection    # RejectionType value or 0
        self.target = target          # reply-to GrainId (ident table)
        self.trace = trace
        self.value = value


class RpcFabric:
    """Batched silo→silo fabric: per-destination egress rings drained
    into sectioned rpc frames (codec.encode_fabric_frame).

    The client→gateway edge already speaks batched zero-copy rpc frames
    (RpcCoalescer above); this extends the same coalescer + columnar
    frame treatment to the intra-cluster edge so remote sends,
    ``try_forward`` reroutes and cross-silo responses all amortize into
    ONE wire frame per (destination, flush) instead of one token-stream
    Message each.

    * **Egress**: ``MessageCenter.send_message`` routes eligible remote
      APPLICATION traffic here (after its breaker gate).  Calls group
      into per-(type, method) sections with the SAME per-sender FIFO
      floor discipline the coalescer's window builder uses — a reroute
      mid-stream never reorders a sender's calls; responses collapse
      into flat results sections.  ``forward_count``, remaining-TTL and
      the trace context ride as per-call columns; TTLs are rebased PER
      CALL on the receiving silo's clock, never frame-level.
    * **Flush**: adaptive — a ring that reaches
      ``rpc_fabric_flush_lanes`` ships inline; otherwise a drain task
      flushes at the next loop-idle point (or after
      ``rpc_fabric_flush_us`` when configured), whichever comes first,
      so single-call latency stays bounded while bulk forwarding
      amortizes.
    * **Ingress**: a decoded frame's call sections enter the receiving
      silo's existing ingress ring (``RpcCoalescer.submit``) and execute
      through ``Dispatcher.invoke_window`` with the pre-resolved invoke
      tables; vector-arena sections fall through to one batched engine
      injection.  Replies are synthesized RESPONSE Messages addressed to
      the per-call reply-to identity — they re-enter ``send_message``
      and batch onto the return fabric, correlating at the origin
      through its own callback table.
    * **Fallback contract**: anything ineligible (string/uuid-keyed
      grains, grain-to-grain calls carrying a call chain, piggybacked
      cache invalidations, non-trace request context) stays on the
      per-message path and is COUNTED (``rpc.fabric_fallbacks``), never
      silent.  A frame that cannot be delivered bounces whole: every
      member request fails immediately as a TRANSIENT rejection (the
      resend machinery re-addresses it — no stranded callers), one-ways
      and responses dead-letter with reason ``undeliverable``.
    """

    def __init__(self, silo) -> None:
        self.silo = silo
        self.cfg = silo.config.rpc  # live object — reload-safe reference
        self._rings: Dict[Any, deque] = {}   # SiloAddress → deque[Message]
        self._flush_task: Optional[asyncio.Task] = None
        self._closing = False
        # cumulative counters (collect_metrics derives interval means)
        self.frames_sent = 0
        self.frames_received = 0
        self.frames_rejected = 0       # undecodable ingress frames
        self.calls_sent = 0
        self.calls_received = 0
        self.results_sent = 0
        self.results_received = 0
        self.fallbacks = 0             # eligible-edge traffic kept per-message
        self.bounced = 0               # members failed by a frame bounce
        self.vector_batches = 0        # sections injected as one engine batch
        self._snap = (0, 0, 0)         # (members, frames) at last collection
        # (type_code, method) pairs known to resolve through the frame
        # ingress tables.  Positive-only memo: a miss re-scans, so late
        # registrations are picked up
        self._resolvable: Dict[Tuple[int, str], bool] = {}
        # direct-path correlation: wire_id → (_Call, dest) for window
        # calls shipped WITHOUT a Message/callback-table entry; results
        # frames resolve these futures straight.  One coarse sweep timer
        # (not a timer per call) enforces caller-side deadlines when the
        # executing silo never answers
        self._direct: Dict[int, Tuple[Any, Any]] = {}
        self._direct_sweep: Optional[asyncio.TimerHandle] = None

    # -- egress -------------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self.cfg.fabric_enabled and not self._closing

    def route(self, msg) -> bool:
        """Called by ``MessageCenter.send_message`` for every remote
        send.  True → the message joined an egress ring and will ship
        inside a fabric frame; False → per-message path (and, for
        fabric-shaped traffic, the fallback counter)."""
        from orleans_tpu.runtime.messaging import Category, is_slab_message
        if not self.enabled:
            return False
        if msg.category != Category.APPLICATION or is_slab_message(msg):
            return False  # system/slab planes keep their own disciplines
        if not self._eligible(msg):
            self.fallbacks += 1
            return False
        ring = self._rings.get(msg.target_silo)
        if ring is None:
            ring = self._rings[msg.target_silo] = deque()
        elif len(ring) >= self.cfg.fabric_max_pending:
            self.fallbacks += 1
            return False  # ring at bound: per-message backpressure path
        ring.append(msg)
        if len(ring) >= self.cfg.fabric_flush_lanes:
            self._flush_dest(msg.target_silo)
            return True
        if self._flush_task is None or self._flush_task.done():
            self._flush_task = asyncio.get_running_loop().create_task(
                self._flush_all())
        return True

    def route_call(self, call) -> bool:
        """Direct egress for a coalesced window's remote-target call:
        ring the ``_Call`` ITSELF when the directory already knows the
        destination — no Message object, no callback-table entry, no
        per-call timeout timer on the hot path.  The returning results
        frame resolves the caller's future straight out of ``_direct``;
        rejections, bounces and deadline lapses materialize the
        per-message Message lazily (the rare paths keep full
        resend/dead-letter semantics).  False → caller stays on the
        per-message net."""
        from orleans_tpu.ids import GrainCategory
        from orleans_tpu.runtime.messaging import _message_ids
        if not self.enabled:
            return False
        gid = call.grain_id
        if gid.category != GrainCategory.GRAIN or gid.key_ext is not None \
                or gid.n0 != 0:
            return False
        if not self._method_packable(gid.type_code, call.method.name):
            return False  # extension/base methods resolve per-message only
        tr = call.trace
        if tr is not None and tr.get("sampled") \
                and not (isinstance(tr.get("trace_id"), int)
                         and tr["trace_id"] > 0):
            return False  # unpackable trace id: per-message, verbatim
        addr = self.silo.grain_directory.try_local_lookup(gid)
        if addr is None or addr.silo == self.silo.address:
            return False  # cold or local-after-all: placement path owns it
        dest = addr.silo
        breakers = self.silo.breakers
        if breakers is not None and not breakers.allow(dest):
            # open breaker: the per-message gate owns the fast-fail (and
            # its dead-letter accounting) — never ship into a known-bad link
            return False
        ring = self._rings.get(dest)
        if ring is None:
            ring = self._rings[dest] = deque()
        elif len(ring) >= self.cfg.fabric_max_pending:
            return False  # ring at bound: per-message backpressure path
        if call.hop:
            # a fabric-ingested call missing here is a REROUTE — it
            # spends a hop exactly like Dispatcher.try_forward, so
            # stale-directory ping-pong stays bounded end to end
            call.forward_count += 1
            dispatcher = self.silo.dispatcher
            if call.forward_count > self.silo.max_forward_count:
                fut = call.future
                if fut is not None and not fut.done():
                    from orleans_tpu.runtime.runtime_client import (
                        RejectionError,
                    )
                    from orleans_tpu.runtime.messaging import RejectionType
                    dispatcher.metrics.rejections_sent += 1
                    fut.set_exception(RejectionError(
                        RejectionType.UNRECOVERABLE,
                        "exceeded max forward count (fabric reroute)"))
                return True  # handled (terminally)
            dispatcher.metrics.messages_forwarded += 1
            if call.forward_count > dispatcher.forward_depth_max:
                dispatcher.forward_depth_max = call.forward_count
        if call.future is not None:
            call.wire_id = next(_message_ids)
            self._direct[call.wire_id] = (call, dest)
            if call.deadline is not None and self._direct_sweep is None:
                loop = asyncio.get_running_loop()
                self._direct_sweep = loop.call_later(
                    0.5, self._sweep_direct)
        ring.append(call)
        if len(ring) >= self.cfg.fabric_flush_lanes:
            self._flush_dest(dest)
            return True
        if self._flush_task is None or self._flush_task.done():
            self._flush_task = asyncio.get_running_loop().create_task(
                self._flush_all())
        return True

    def _sweep_direct(self) -> None:
        """Coarse caller-side deadline net for direct calls (the far
        silo normally answers — including expiry rejections — before
        this fires; it exists so a wedged peer can't strand a caller)."""
        self._direct_sweep = None
        if not self._direct or self._closing:
            return
        now = time.monotonic()
        lapsed = [mid for mid, (c, _d) in self._direct.items()
                  if c.deadline is not None and now > c.deadline]
        breakers = self.silo.breakers
        for mid in lapsed:
            call, dest = self._direct.pop(mid)
            # an unanswered direct call is a timeout AGAINST that silo —
            # the same closed→open signal the per-message timer feeds
            if breakers is not None and dest != self.silo.address:
                breakers.record_failure(dest, "request timeout")
            self.silo.dispatcher._expire_call(call)
        if self._direct:
            self._direct_sweep = asyncio.get_event_loop().call_later(
                0.5, self._sweep_direct)

    def _eligible(self, msg) -> bool:
        from orleans_tpu.ids import GrainCategory
        from orleans_tpu.runtime.messaging import Direction
        from orleans_tpu.spans import TRACE_KEY
        if msg.target_grain is None or msg.cache_invalidation \
                or msg.timestamps:
            return False
        rc = msg.request_context
        if rc:
            if set(rc) != {TRACE_KEY}:
                return False  # only the trace context has a frame column
            tr = rc[TRACE_KEY]
            tid = tr.get("trace_id") if isinstance(tr, dict) else None
            if (isinstance(tr, dict) and tr.get("sampled")
                    and not (isinstance(tid, int) and tid > 0)):
                # externally-pinned non-integer trace ids cannot ride
                # the packed u64 column: keep the message per-message so
                # the trace survives verbatim (counted, never truncated)
                return False
        if msg.direction == Direction.RESPONSE:
            return True   # correlated by id at the destination
        g = msg.target_grain
        if g.category != GrainCategory.GRAIN or g.key_ext is not None \
                or g.n0 != 0:
            return False  # key column is one u64 word
        if msg.call_chain or msg.sending_activation is not None:
            return False  # grain-to-grain chains keep per-message semantics
        if msg.is_new_placement or msg.is_unordered or not msg.method_name:
            return False
        if msg.sending_grain is None:
            return False
        return self._method_packable(g.type_code, msg.method_name)

    def _method_packable(self, type_code: int, method_name: str) -> bool:
        # the frame ingress resolves calls by (type_code, method_name)
        # through the interface tables; extension methods living only on
        # the Grain base (stream_deliver etc.) resolve per-message via
        # getattr — keep them there
        rkey = (type_code, method_name)
        if self._resolvable.get(rkey, False):
            return True
        from orleans_tpu.tensor.vector_grain import vector_type
        vt = vector_type(type_code)
        ok = ((vt is not None and method_name in vt.methods)
              or self._resolve_method(type_code, method_name)[0] is not None)
        if ok:
            self._resolvable[rkey] = True
        return ok

    async def _flush_all(self) -> None:
        us = self.cfg.fabric_flush_us
        if us > 0:
            # amortization backstop: hold small batches up to flush_us
            # (lanes-full rings already shipped inline from route())
            await asyncio.sleep(us / 1e6)
        while True:
            pending = [d for d, r in self._rings.items() if r]
            if not pending:
                return
            for dest in pending:
                self._flush_dest(dest)

    def _flush_dest(self, dest) -> None:
        ring = self._rings.get(dest)
        if not ring:
            return
        items = list(ring)
        ring.clear()
        self._ship(dest, items)

    def _ship(self, dest, items: list) -> None:
        from orleans_tpu.codec import default_manager, encode_fabric_frame
        from orleans_tpu.runtime.messaging import (
            Category,
            Direction,
            FABRIC_METHOD,
            Message,
        )
        transport = self.silo.message_center.transport
        if transport is None:
            self._fail_items(dest, items, "no transport attached")
            return
        idents, sections, n_calls, n_results = self._build_sections(items)
        try:
            segments = encode_fabric_frame(default_manager,
                                           self.silo.address, idents,
                                           sections)
        except Exception as exc:  # noqa: BLE001 — an unencodable member
            # must not strand its frame-mates: the whole batch takes the
            # per-message path (each message degrades/bounces alone)
            self.silo.logger.warn(
                f"fabric frame encode to {dest} failed: {exc!r}; "
                f"falling back per-message for {len(items)} sends",
                code=2930)
            self.fallbacks += len(items)
            loop = asyncio.get_running_loop()
            for it in items:
                t = type(it)
                if t is _Call:
                    if it.wire_id is not None:
                        self._direct.pop(it.wire_id, None)
                        it.wire_id = None
                    self.silo.dispatcher._window_fallback(it, loop)
                elif t is _Result:
                    transport.send(self._materialize_result(it, dest))
                else:
                    transport.send(it)
            return
        carrier = Message(category=Category.APPLICATION,
                          direction=Direction.ONE_WAY,
                          sending_silo=self.silo.address,
                          target_silo=dest, method_name=FABRIC_METHOD)
        carrier._fabric_segments = segments
        carrier._fabric_items = items
        self.frames_sent += 1
        self.calls_sent += n_calls
        self.results_sent += n_results
        transport.send(carrier)

    def _build_sections(self, items: list):
        """Group ring items into frame sections.  Calls use the window
        builder's per-sender floor algorithm — a sender's later call
        never lands in an EARLIER section — so the receiving coalescer
        replays them in order and per-sender FIFO holds end to end.
        Responses collapse into flat results sections."""
        from orleans_tpu.codec import (
            FABRIC_NO_TTL,
            FABRIC_RESULT_ERROR,
            FABRIC_RESULT_OK,
            FABRIC_RESULT_REJECTION,
            FabricCallsSection,
            FabricResultsSection,
            pack_rpc_trace,
        )
        from orleans_tpu.runtime.messaging import Direction, ResponseKind
        from orleans_tpu.spans import trace_of
        idents: list = []
        ident_idx: Dict[Any, int] = {}

        def intern(obj) -> int:
            i = ident_idx.get(obj)
            if i is None:
                i = len(idents)
                idents.append(obj)
                ident_idx[obj] = i
            return i

        now = time.monotonic()
        max_window = self.cfg.max_window
        accs: list = []               # per-section accumulator dicts
        open_by_key: Dict[Tuple[int, str, bool], int] = {}
        sender_floor: Dict[Any, int] = {}
        results_at: int = -1
        n_calls = n_results = 0
        self_ident = (self.silo.address, self.silo.client_grain_id)

        def results_acc() -> dict:
            nonlocal results_at
            if results_at < 0:
                results_at = len(accs)
                accs.append({"kind": "results", "msg_ids": [],
                             "statuses": [], "rejections": [],
                             "targets": [], "traces": [],
                             "values": []})
            return accs[results_at]

        def calls_acc(type_code: int, method_name: str, one_way: bool,
                      floor_key: Any) -> dict:
            key = (type_code, method_name, one_way)
            wi = open_by_key.get(key, -1)
            floor = sender_floor.get(floor_key, -1)
            if wi < 0 or wi < floor or len(accs[wi]["keys"]) >= max_window:
                wi = len(accs)
                accs.append({"kind": "calls", "type_code": type_code,
                             "method_name": method_name,
                             "one_way": one_way, "keys": [],
                             "msg_ids": [], "ttls": [], "fwds": [],
                             "senders": [], "traces": [], "args": []})
                open_by_key[key] = wi
            sender_floor[floor_key] = wi
            return accs[wi]

        for it in items:
            t = type(it)
            if t is _Call:
                # direct-path item: the window's own call object
                one_way = it.future is None
                gid = it.grain_id
                acc = calls_acc(gid.type_code, it.method.name, one_way,
                                it.sender if it.sender is not None
                                else self_ident)
                acc["keys"].append(gid.n1)
                acc["msg_ids"].append(it.wire_id or 0)
                acc["ttls"].append(FABRIC_NO_TTL if it.deadline is None
                                   else max(0.0, it.deadline - now))
                acc["fwds"].append(it.forward_count)
                acc["senders"].append(intern(self_ident))
                acc["traces"].append(it.trace)
                acc["args"].append(it.args)
                n_calls += 1
                continue
            if t is _Result:
                acc = results_acc()
                acc["msg_ids"].append(it.msg_id)
                acc["statuses"].append(it.status)
                acc["rejections"].append(it.rejection)
                acc["targets"].append(intern(it.target))
                acc["traces"].append(it.trace)
                acc["values"].append(it.value)
                n_results += 1
                continue
            if it.direction == Direction.RESPONSE:
                acc = results_acc()
                kind = it.response_kind
                if kind == ResponseKind.REJECTION:
                    status = FABRIC_RESULT_REJECTION
                    value = it.rejection_info
                    rej = int(it.rejection_type or 0)
                elif kind == ResponseKind.ERROR:
                    status = FABRIC_RESULT_ERROR
                    value = it.result
                    rej = 0
                else:
                    status = FABRIC_RESULT_OK
                    value = it.result
                    rej = 0
                acc["msg_ids"].append(it.id)
                acc["statuses"].append(status)
                acc["rejections"].append(rej)
                acc["targets"].append(intern(it.target_grain))
                acc["traces"].append(trace_of(it))
                acc["values"].append(value)
                n_results += 1
                continue
            one_way = it.direction == Direction.ONE_WAY
            acc = calls_acc(it.target_grain.type_code, it.method_name,
                            one_way, it.sending_grain)
            acc["keys"].append(it.target_grain.n1)
            acc["msg_ids"].append(it.id)
            acc["ttls"].append(FABRIC_NO_TTL if it.expiration is None
                               else max(0.0, it.expiration - now))
            acc["fwds"].append(it.forward_count)
            acc["senders"].append(intern((it.sending_silo,
                                          it.sending_grain)))
            acc["traces"].append(trace_of(it))
            acc["args"].append(it.args)
            n_calls += 1
        sections: list = []
        for acc in accs:
            traces = acc["traces"]
            trace_ids = span_ids = None
            if any(t is not None for t in traces):
                trace_ids = [pack_rpc_trace(t) for t in traces]
                span_ids = [(t.get("span_id") if t else 0) or 0
                            for t in traces]
                span_ids = [s if isinstance(s, int) else 0
                            for s in span_ids]
            if acc["kind"] == "results":
                sections.append(FabricResultsSection(
                    msg_ids=acc["msg_ids"], statuses=acc["statuses"],
                    rejections=acc["rejections"], targets=acc["targets"],
                    trace_ids=trace_ids, span_ids=span_ids,
                    values=acc["values"]))
            else:
                sections.append(FabricCallsSection(
                    acc["type_code"], acc["method_name"], acc["one_way"],
                    keys=acc["keys"], msg_ids=acc["msg_ids"],
                    ttls=acc["ttls"], forward_counts=acc["fwds"],
                    senders=acc["senders"], trace_ids=trace_ids,
                    span_ids=span_ids, args_list=acc["args"]))
        return idents, sections, n_calls, n_results

    # -- ingress ------------------------------------------------------------

    def on_frame_payload(self, payload) -> None:
        """One arriving fabric frame body (transport already stripped
        the magic/length header)."""
        from orleans_tpu.codec import (
            FabricCallsSection,
            SerializationError,
            decode_fabric_frame,
            default_manager,
        )
        try:
            frame = decode_fabric_frame(default_manager, bytes(payload))
        except SerializationError as exc:
            self.frames_rejected += 1
            self.silo.logger.warn(
                f"dropping undecodable fabric frame: {exc!r}", code=2931)
            return
        self.frames_received += 1
        for sec in frame.sections:
            if isinstance(sec, FabricCallsSection):
                self._ingest_calls(frame.idents, sec)
            else:
                self._ingest_results(frame.origin, frame.idents, sec)

    def _ingest_calls(self, idents: list, sec) -> None:
        from orleans_tpu.codec import unpack_rpc_trace
        from orleans_tpu.tensor.vector_grain import vector_type
        silo = self.silo
        self.calls_received += sec.n
        if vector_type(sec.type_code) is not None:
            self._ingest_vector_calls(idents, sec)
            return
        minfo, iface_id = self._resolve_method(sec.type_code,
                                               sec.method_name)
        now = time.monotonic()
        loop = asyncio.get_running_loop()
        coal = silo.rpc
        accepting = coal.accepting()
        dispatcher = silo.dispatcher
        common = sec.common_args
        for i in range(sec.n):
            reply_silo, reply_grain = idents[int(sec.senders[i])]
            ttl = float(sec.ttls[i])
            deadline = None if ttl < 0 else now + ttl
            trace = None
            if sec.trace_ids is not None:
                trace = unpack_rpc_trace(int(sec.trace_ids[i]),
                                         int(sec.span_ids[i]))
            gid = GrainId.from_int(sec.type_code, int(sec.keys[i]))
            args = common if common is not None else sec.args_list[i]
            if minfo is None:
                # unknown (type, method) on this silo: answer what can
                # be answered, never strand the caller
                self._reply_unresolvable(reply_silo, reply_grain,
                                         int(sec.msg_ids[i]), sec, trace)
                continue
            fut = None
            if not sec.one_way:
                fut = loop.create_future()
                fut.add_done_callback(self._make_relay(
                    reply_silo, reply_grain, int(sec.msg_ids[i]), trace))
            call = _Call(gid, minfo, iface_id, tuple(args), fut, deadline,
                         reply_grain, trace,
                         forward_count=int(sec.forward_counts[i]))
            call.hop = True   # re-dispatching this call spends a hop
            if accepting:
                coal.submit(call)
            else:
                dispatcher._window_fallback(call, loop)

    def _ingest_vector_calls(self, idents: list, sec) -> None:
        """Vector-arena sections fall through to the tensor engine: a
        uniform one-way section becomes ONE batched injection (the
        router ships non-owned keys onward as slabs); request sections
        relay per-call result futures back over the fabric."""
        import numpy as np

        from orleans_tpu.codec import unpack_rpc_trace
        from orleans_tpu.tensor.vector_grain import vector_type
        silo = self.silo
        engine = getattr(silo, "tensor_engine", None)
        vt = vector_type(sec.type_code)
        minfo = vt.methods.get(sec.method_name) if vt is not None else None
        if engine is None or minfo is None:
            for i in range(sec.n):
                reply_silo, reply_grain = idents[int(sec.senders[i])]
                trace = None
                if sec.trace_ids is not None:
                    trace = unpack_rpc_trace(int(sec.trace_ids[i]),
                                             int(sec.span_ids[i]))
                self._reply_unresolvable(reply_silo, reply_grain,
                                         int(sec.msg_ids[i]), sec, trace)
            return
        if sec.one_way and sec.common_args is not None:
            import jax

            payload = sec.common_args[0] if sec.common_args else {}
            n = sec.n
            batch = jax.tree_util.tree_map(
                lambda x: np.ascontiguousarray(np.broadcast_to(
                    np.asarray(x)[None], (n,) + np.asarray(x).shape)),
                payload)
            engine.send_batch(vt.name, sec.method_name,
                              np.asarray(sec.keys, dtype=np.int64), batch)
            self.vector_batches += 1
            return
        for i in range(sec.n):
            gid = GrainId.from_int(sec.type_code, int(sec.keys[i]))
            args = sec.common_args if sec.common_args is not None \
                else sec.args_list[i]
            fut = engine.send_one(gid, minfo, tuple(args))
            if fut is not None and not sec.one_way:
                reply_silo, reply_grain = idents[int(sec.senders[i])]
                trace = None
                if sec.trace_ids is not None:
                    trace = unpack_rpc_trace(int(sec.trace_ids[i]),
                                             int(sec.span_ids[i]))
                fut.add_done_callback(self._make_relay(
                    reply_silo, reply_grain, int(sec.msg_ids[i]), trace))

    @staticmethod
    def _resolve_method(type_code: int, method_name: str):
        info = type_registry.by_type_code.get(type_code)
        if info is None:
            return None, 0
        for iface in info.interfaces:
            m = iface.methods_by_name.get(method_name)
            if m is not None:
                return m, iface.interface_id
        return None, 0

    def _make_relay(self, reply_silo, reply_grain, msg_id: int,
                    trace) -> Callable:
        """Done-callback for an ingested call's future: ring a bare
        ``_Result`` row addressed to the ORIGINAL sender (a forwarded
        call replies directly, no detour through the forwarder) — it
        batches onto the return fabric without ever building a RESPONSE
        Message.  When the fabric can't take it (disabled mid-flight,
        ring at bound, local sender) the Message materializes and
        re-enters send_message as before."""

        def _relay(fut: asyncio.Future) -> None:
            from orleans_tpu.codec import (
                FABRIC_RESULT_ERROR,
                FABRIC_RESULT_OK,
                FABRIC_RESULT_REJECTION,
            )
            from orleans_tpu.runtime.runtime_client import RejectionError
            status = FABRIC_RESULT_OK
            value: Any = None
            rej = 0
            if fut.cancelled():
                from orleans_tpu.runtime.messaging import RejectionType
                status = FABRIC_RESULT_REJECTION
                rej = int(RejectionType.TRANSIENT)
                value = "request cancelled on executing silo"
            else:
                exc = fut.exception()
                if exc is None:
                    value = fut.result()
                elif isinstance(exc, RejectionError):
                    status = FABRIC_RESULT_REJECTION
                    rej = int(exc.rejection)
                    value = exc.info
                else:
                    status = FABRIC_RESULT_ERROR
                    value = exc
            res = _Result(msg_id, status, rej, reply_grain, trace, value)
            if self.enabled and reply_silo != self.silo.address:
                ring = self._rings.get(reply_silo)
                if ring is None:
                    ring = self._rings[reply_silo] = deque()
                if len(ring) < self.cfg.fabric_max_pending:
                    ring.append(res)
                    if len(ring) >= self.cfg.fabric_flush_lanes:
                        self._flush_dest(reply_silo)
                    elif (self._flush_task is None
                          or self._flush_task.done()):
                        self._flush_task = \
                            asyncio.get_running_loop().create_task(
                                self._flush_all())
                    return
            self.silo.message_center.send_message(
                self._materialize_result(res, reply_silo))

        return _relay

    def _materialize_result(self, res: "_Result", reply_silo):
        """Build the RESPONSE Message a ``_Result`` row stands in for —
        the fallback/dead-letter paths need the real object."""
        from orleans_tpu.codec import (
            FABRIC_RESULT_ERROR,
            FABRIC_RESULT_REJECTION,
        )
        from orleans_tpu.runtime.messaging import (
            Category,
            Direction,
            Message,
            RejectionType,
            ResponseKind,
        )
        from orleans_tpu.spans import TRACE_KEY
        kind = ResponseKind.SUCCESS
        result: Any = res.value
        rej_type = None
        rej_info = ""
        if res.status == FABRIC_RESULT_REJECTION:
            kind = ResponseKind.REJECTION
            result = None
            try:
                rej_type = RejectionType(res.rejection)
            except ValueError:
                rej_type = RejectionType.UNRECOVERABLE
            rej_info = str(res.value)
        elif res.status == FABRIC_RESULT_ERROR:
            kind = ResponseKind.ERROR
        msg = Message(category=Category.APPLICATION,
                      direction=Direction.RESPONSE, id=res.msg_id,
                      sending_silo=self.silo.address,
                      target_silo=reply_silo,
                      target_grain=res.target,
                      response_kind=kind, result=result,
                      rejection_type=rej_type, rejection_info=rej_info)
        if res.trace is not None:
            msg.request_context = {TRACE_KEY: dict(res.trace)}
        return msg

    def _reply_unresolvable(self, reply_silo, reply_grain, msg_id: int,
                            sec, trace) -> None:
        from orleans_tpu.runtime.messaging import (
            Category,
            Direction,
            Message,
            RejectionType,
            ResponseKind,
        )
        from orleans_tpu.spans import TRACE_KEY
        if sec.one_way:
            return
        msg = Message(category=Category.APPLICATION,
                      direction=Direction.RESPONSE, id=msg_id,
                      sending_silo=self.silo.address,
                      target_silo=reply_silo, target_grain=reply_grain,
                      response_kind=ResponseKind.REJECTION,
                      rejection_type=RejectionType.UNRECOVERABLE,
                      rejection_info=f"no grain method "
                                     f"{sec.type_code}.{sec.method_name} "
                                     f"registered on {self.silo.address}")
        if trace is not None:
            msg.request_context = {TRACE_KEY: dict(trace)}
        self.silo.message_center.send_message(msg)

    def _ingest_results(self, origin, idents: list, sec) -> None:
        from orleans_tpu.codec import (
            FABRIC_RESULT_ERROR,
            FABRIC_RESULT_REJECTION,
            unpack_rpc_trace,
        )
        from orleans_tpu.runtime.messaging import (
            Category,
            Direction,
            Message,
            RejectionType,
            ResponseKind,
        )
        from orleans_tpu.spans import TRACE_KEY
        silo = self.silo
        self.results_received += sec.n
        # a results frame from the origin IS its silo answering — the
        # per-message path's record_success seam, once per section
        if silo.breakers is not None and origin != silo.address:
            silo.breakers.record_success(origin)
        direct = self._direct
        for i in range(sec.n):
            status = int(sec.statuses[i])
            value = sec.values[i]
            ent = direct.pop(int(sec.msg_ids[i]), None)
            if ent is not None:
                # direct-path correlation: resolve the window call's
                # future straight — no RESPONSE Message, no callback
                # table.  Rejections re-enter the per-message net so
                # resend/fail semantics stay identical
                call, _dest = ent
                fut = call.future
                if fut is None or fut.done():
                    continue
                if status == FABRIC_RESULT_REJECTION:
                    self._redispatch_rejected(call,
                                              int(sec.rejections[i]),
                                              str(value))
                elif status == FABRIC_RESULT_ERROR:
                    fut.set_exception(
                        value if isinstance(value, BaseException)
                        else RuntimeError(str(value)))
                else:
                    fut.set_result(value)
                continue
            kind = ResponseKind.SUCCESS
            result: Any = value
            rej_type = None
            rej_info = ""
            if status == FABRIC_RESULT_REJECTION:
                kind = ResponseKind.REJECTION
                result = None
                try:
                    rej_type = RejectionType(int(sec.rejections[i]))
                except ValueError:
                    rej_type = RejectionType.UNRECOVERABLE
                rej_info = str(value)
            elif status == FABRIC_RESULT_ERROR:
                kind = ResponseKind.ERROR
                if not isinstance(value, BaseException):
                    # the exception degraded at encode — surface as a
                    # typed error, never a set_exception(str) crash
                    result = RuntimeError(str(value))
            msg = Message(category=Category.APPLICATION,
                          direction=Direction.RESPONSE,
                          id=int(sec.msg_ids[i]),
                          sending_silo=origin, target_silo=silo.address,
                          target_grain=idents[int(sec.targets[i])],
                          response_kind=kind, result=result,
                          rejection_type=rej_type, rejection_info=rej_info)
            if sec.trace_ids is not None:
                trace = unpack_rpc_trace(int(sec.trace_ids[i]),
                                         int(sec.span_ids[i]))
                if trace is not None:
                    msg.request_context = {TRACE_KEY: trace}
            silo.message_center.deliver_local(msg)

    def _redispatch_rejected(self, call, rej_code: int,
                             info: str) -> None:
        """A direct-path call came back REJECTED.  TRANSIENT rejections
        re-enter the per-message net (cache invalidated first, one
        retry-budget token spent — same amplification discipline as
        CallbackData resends); everything else fails the caller with the
        same typed RejectionError the per-message path raises."""
        from orleans_tpu.runtime.messaging import RejectionType
        from orleans_tpu.runtime.runtime_client import RejectionError
        silo = self.silo
        try:
            rt = RejectionType(rej_code)
        except ValueError:
            rt = RejectionType.UNRECOVERABLE
        if rt == RejectionType.TRANSIENT \
                and silo.runtime_client.resend_on_transient \
                and (call.deadline is None
                     or time.monotonic() < call.deadline):
            if silo.retry_budget.try_spend():
                silo.metrics.requests_resent += 1
                silo.grain_directory.cache.invalidate(call.grain_id)
                silo.dispatcher._window_fallback(
                    call, asyncio.get_running_loop())
                return
            silo.metrics.retries_denied += 1
        fut = call.future
        if fut is not None and not fut.done():
            fut.set_exception(RejectionError(rt, info))

    # -- failure handling ---------------------------------------------------

    def on_frame_bounce(self, carrier, reason: str) -> None:
        """A shipped frame could not be delivered (link failure, peer
        declared dead mid-flush).  Every member request fails NOW as a
        TRANSIENT rejection — the resend machinery re-addresses it under
        its hop/retry budget, no caller waits out its deadline."""
        items = getattr(carrier, "_fabric_items", None)
        if items:
            self._fail_items(carrier.target_silo, items, reason)

    def _fail_items(self, dest, items: list, reason: str) -> None:
        from orleans_tpu.resilience import REASON_UNDELIVERABLE
        from orleans_tpu.runtime.messaging import Direction, RejectionType
        silo = self.silo
        self.bounced += len(items)
        loop = None
        for it in items:
            t = type(it)
            if t is _Call:
                # direct-path member: re-address through the per-message
                # net NOW (no caller waits out a deadline on a dead link)
                if it.wire_id is not None:
                    self._direct.pop(it.wire_id, None)
                    it.wire_id = None
                silo.grain_directory.cache.invalidate(it.grain_id)
                if it.future is not None and it.future.done():
                    continue
                if loop is None:
                    loop = asyncio.get_event_loop()
                silo.dispatcher._window_fallback(it, loop)
                continue
            if t is _Result:
                it = self._materialize_result(it, dest)
            if it.direction == Direction.REQUEST:
                silo.message_center.send_message(it.create_rejection(
                    RejectionType.TRANSIENT,
                    f"fabric frame to {dest} undeliverable: {reason}"))
            else:
                if silo.dead_letters is not None:
                    silo.dead_letters.record(it, REASON_UNDELIVERABLE,
                                             f"fabric frame: {reason}")
                if silo.metrics is not None:
                    silo.metrics.undeliverable_dropped += 1

    def fail_destination(self, dest, reason: str) -> None:
        """Silo-death hook: fail everything still ringed for ``dest``
        (carriers already handed to the transport bounce through
        ``on_frame_bounce`` when the transport prunes the link), then
        every SHIPPED direct call still awaiting a result from it —
        nobody strands on a dead silo's unanswered frame."""
        ring = self._rings.pop(dest, None)
        if ring:
            self._fail_items(dest, list(ring), reason)
        stranded = [mid for mid, (_c, d) in self._direct.items()
                    if d == dest]
        if stranded:
            calls = [self._direct.pop(mid)[0] for mid in stranded]
            self._fail_items(dest, calls, reason)

    def prune_dead(self, live) -> None:
        for dest in [d for d in self._rings if d not in live]:
            self.fail_destination(dest, f"silo {dest} declared dead")

    def close_nowait(self) -> None:
        self._closing = True
        self._rings.clear()
        self._direct.clear()
        if self._direct_sweep is not None:
            self._direct_sweep.cancel()
            self._direct_sweep = None

    # -- settle / telemetry -------------------------------------------------

    def pending(self) -> int:
        return sum(len(r) for r in self._rings.values())

    async def wait_idle(self) -> None:
        """Settle helper (tests): resolve when every egress ring has
        flushed and the drain task has finished."""
        while self.pending() or (self._flush_task is not None
                                 and not self._flush_task.done()):
            task = self._flush_task
            if task is not None and not task.done():
                await asyncio.shield(task)
            else:
                await asyncio.sleep(0)

    def snapshot(self) -> Dict[str, Any]:
        """Pure read — interval gauges come from collect_interval()."""
        members = self.calls_sent + self.results_sent
        return {
            "frames_sent": self.frames_sent,
            "frames_received": self.frames_received,
            "frames_rejected": self.frames_rejected,
            "calls_sent": self.calls_sent,
            "calls_received": self.calls_received,
            "results_sent": self.results_sent,
            "results_received": self.results_received,
            "fallbacks": self.fallbacks,
            "bounced": self.bounced,
            "vector_batches": self.vector_batches,
            "egress_batch": (members / self.frames_sent)
            if self.frames_sent else 0.0,
            "pending": self.pending(),
        }

    def collect_interval(self) -> Dict[str, float]:
        """Interval mean members-per-frame since the previous collection
        (owned by ``silo.collect_metrics`` alone)."""
        members = self.calls_sent + self.results_sent
        frames = self.frames_sent
        p_members, p_frames, _ = self._snap
        self._snap = (members, frames, 0)
        df = frames - p_frames
        return {
            "egress_batch": ((members - p_members) / df) if df else 0.0,
        }


# ===========================================================================
# multi-process proof harness (tentpole leg 4)
# ===========================================================================
#
# Real processes, real sockets, no shared memory: a silo SERVER process
# (optionally clustered through a table-service process — the
# no-shared-disk membership path plugins/table_service.py exists for)
# and a client DRIVER process dialing the gateway port.  Both print one
# JSON line on stdout; the server then serves until stdin closes, so an
# exiting parent always reaps it.  The tests/test_rpc.py
# multiprocess smoke spawns these.

def _serve_main(args) -> int:
    import json
    import sys

    import samples.helloworld  # noqa: F401 — registers IHello/HelloGrain

    from orleans_tpu.config import SiloConfig
    from orleans_tpu.runtime.silo import Silo

    async def main() -> None:
        cfg = SiloConfig(name=args.name)
        cfg.liveness.probe_period = 0.2
        cfg.liveness.probe_timeout = 0.5
        cfg.liveness.table_refresh_timeout = 0.3
        cfg.liveness.iam_alive_table_publish = 0.5
        cfg.rpc.fastpath_enabled = not args.no_fastpath
        cfg.rpc.fabric_enabled = not args.no_fabric
        cfg.tracing.enabled = not args.no_tracing
        cfg.tracing.sample_rate = args.trace_sample_rate
        from orleans_tpu.runtime.transport import TcpFabric

        # gateway silos need a real TCP endpoint (the acceptor only
        # listens on routable silos) — single-silo servers bind one too
        fabric = TcpFabric()
        host, port = fabric.host, fabric.reserve()
        table_service = None
        membership = None
        if args.host_table_service or args.table_service:
            # clustered mode: membership over TCP (no shared disk)
            from orleans_tpu.plugins.table_service import (
                RemoteMembershipTable,
                TableServiceServer,
            )
            if args.host_table_service:
                table_service = await TableServiceServer().start()
                ts_host, ts_port = table_service.address
            else:
                ts_host, _, p = args.table_service.rpartition(":")
                ts_port = int(p)
            membership = RemoteMembershipTable(ts_host, ts_port)
        silo = Silo(config=cfg, fabric=fabric, membership_table=membership,
                    host=host, port=port)
        await silo.start()
        # server-process GC policy: freeze the started runtime and relax
        # the gen0 cadence — the default collector re-scans every
        # in-flight window's futures every ~700 allocations (measured
        # ~40% of the batched host path); standard asyncio-server tuning
        import gc

        gc.collect()
        gc.freeze()
        gc.set_threshold(100_000, 50, 50)
        import jax

        print(json.dumps({
            "ok": True, "name": silo.name,
            # the platform this process's engine runs on, so numbers
            # harvested from it never read as another process's device
            "platform": jax.devices()[0].platform,
            "gateway_port": silo.gateway_port,
            "table_service_port": (table_service.address[1]
                                   if table_service is not None else 0),
        }), flush=True)
        # serve until the parent closes our stdin (portable lifetime tie)
        loop = asyncio.get_running_loop()
        closed = loop.create_future()
        try:
            def _eof() -> None:
                if not closed.done():
                    closed.set_result(None)
            loop.add_reader(sys.stdin.fileno(), _eof)
        except (ValueError, OSError):
            pass  # no usable stdin: fall back to sleeping forever
        try:
            await closed
        finally:
            try:
                # one last JSON line before exit: the silo→silo fabric
                # evidence the parent bench harvests into its artifact
                fs = silo.rpc_fabric.snapshot()
                print(json.dumps({
                    "final": True, "name": silo.name,
                    "forwarded": silo.metrics.messages_forwarded,
                    "fabric": {k: fs[k] for k in (
                        "frames_sent", "frames_received",
                        "frames_rejected", "calls_sent",
                        "calls_received", "results_sent",
                        "results_received", "fallbacks", "bounced")},
                }), flush=True)
            except Exception:  # noqa: BLE001 — stats are best-effort
                pass
            if args.timeline_dir:
                # file-handoff timeline collection: drop this silo's
                # export for `python -m orleans_tpu.timeline` to merge
                import os
                os.makedirs(args.timeline_dir, exist_ok=True)
                path = os.path.join(args.timeline_dir,
                                    f"timeline_{silo.name}.json")
                with open(path, "w") as f:
                    json.dump(silo.spans.timeline.export(), f)
            await silo.stop(graceful=False)
            if table_service is not None:
                table_service.close()

    asyncio.run(main())
    return 0


def _drive_main(args) -> int:
    import json

    from samples.helloworld import IHello

    from orleans_tpu.client import GrainClient
    from orleans_tpu.config import ClientConfig

    async def main() -> Dict[str, Any]:
        cfg = ClientConfig(rpc_fastpath=not args.no_fastpath,
                           trace_sample_rate=args.trace_sample_rate)
        client = GrainClient.from_config(cfg)
        endpoints = []
        for ep in args.gateways.split(","):
            h, _, p = ep.rpartition(":")
            endpoints.append((h or "127.0.0.1", int(p)))
        await client.connect(*endpoints)
        try:
            refs = [client.get_grain(IHello, args.key_base + i)
                    for i in range(args.grains)]
            # warm: activations + invoke tables + rpc dictionary
            await asyncio.gather(*(r.say_hello("warm") for r in refs))
            # driver-process GC tuning (mirrors the server's — see
            # _serve_main; the measured segment is allocation-heavy)
            import gc

            gc.collect()
            gc.freeze()
            gc.set_threshold(100_000, 50, 50)
            expect = [f"You said: 'hi-{i % 7}', I say: Hello!"
                      for i in range(args.grains)]
            exact = True
            # untimed steady-state ramp: the first pipelined rounds pay
            # one-time costs on every hop (directory caches on both
            # silos, fabric rings, branch-warm codec paths) — the timed
            # segment measures the operating point, not the ramp
            for _ in range(3):
                futs = [refs[i].say_hello(f"hi-{i % 7}")
                        for i in range(args.grains)]
                exact = exact and [await f for f in futs] == expect
            inflight = max(1, args.inflight)
            pending: list = []
            t0 = time.perf_counter()
            for _ in range(args.rounds):
                # pipelined harvest: issue the round, await replies in
                # issue order (a window's replies resolve together).
                # --inflight > 1 keeps that many rounds outstanding so
                # cross-process handoffs overlap instead of serializing
                # on scheduler wakeups (per-grain FIFO still holds: a
                # grain's round-N call precedes its round-N+1 call)
                futs = [refs[i].say_hello(f"hi-{i % 7}")
                        for i in range(args.grains)]
                pending.append(futs)
                if len(pending) >= inflight:
                    got = [await f for f in pending.pop(0)]
                    exact = exact and got == expect
            while pending:
                got = [await f for f in pending.pop(0)]
                exact = exact and got == expect
            elapsed = time.perf_counter() - t0
            calls = args.grains * args.rounds
            # serialized single-call probes (each call awaited before the
            # next is issued) — the latency-regression arm of the fabric
            # A/B: ringed sends must still flush at loop idle, so a lone
            # call never waits out a batch timer
            p50 = None
            if args.latency_probes > 0:
                lat = []
                probe_expect = "You said: 'ping', I say: Hello!"
                for j in range(args.latency_probes):
                    r = refs[j % len(refs)]
                    c0 = time.perf_counter()
                    got_p = await r.say_hello("ping")
                    lat.append(time.perf_counter() - c0)
                    exact = exact and got_p == probe_expect
                p50 = sorted(lat)[len(lat) // 2]
            return {"ok": True, "exact": bool(exact), "calls": calls,
                    "elapsed_s": elapsed,
                    "rpc_per_sec": calls / elapsed if elapsed else 0.0,
                    "single_call_p50_s": p50}
        finally:
            await client.close()

    out = asyncio.run(main())
    print(json.dumps(out), flush=True)
    return 0 if out.get("ok") and out.get("exact") else 1


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m orleans_tpu.runtime.rpc",
        description="multi-process host-RPC proof harness "
                    "(silo server / client driver processes)")
    sub = parser.add_subparsers(dest="cmd", required=True)
    serve = sub.add_parser("serve", help="run one gateway silo process")
    serve.add_argument("--name", default="rpc-silo")
    serve.add_argument("--no-fastpath", action="store_true")
    serve.add_argument("--no-fabric", action="store_true",
                       help="disable the batched silo→silo fabric (the "
                            "per-message A/B arm)")
    serve.add_argument("--host-table-service", action="store_true",
                       help="also host the cluster membership table "
                            "service (first silo of a cluster)")
    serve.add_argument("--table-service", default=None,
                       help="host:port of an existing table service to "
                            "join (subsequent silos of a cluster)")
    serve.add_argument("--no-tracing", action="store_true",
                       help="disable the span/timeline plane entirely "
                            "(overhead A/B control arm)")
    serve.add_argument("--trace-sample-rate", type=float, default=0.01,
                       help="head-sampling rate for traces minted on "
                            "this silo (default 0.01)")
    serve.add_argument("--timeline-dir", default="",
                       help="write timeline_<name>.json here at "
                            "shutdown (merge with python -m "
                            "orleans_tpu.timeline)")
    drive = sub.add_parser("drive", help="run one client driver process")
    drive.add_argument("--gateways", required=True,
                       help="comma-separated host:port gateway endpoints")
    drive.add_argument("--grains", type=int, default=500)
    drive.add_argument("--rounds", type=int, default=5)
    drive.add_argument("--key-base", type=int, default=41000)
    drive.add_argument("--inflight", type=int, default=1,
                       help="rounds kept outstanding before harvesting "
                            "(amortizes cross-process scheduler "
                            "handoffs; per-grain call order unchanged)")
    drive.add_argument("--latency-probes", type=int, default=0,
                       help="after the throughput rounds, issue this "
                            "many strictly-serialized calls and report "
                            "their p50 (the fabric's single-call "
                            "latency gate)")
    drive.add_argument("--no-fastpath", action="store_true")
    drive.add_argument("--trace-sample-rate", type=float, default=0.0,
                       help="client-side head-sampling rate (sampled "
                            "calls ride the rpc trace column)")
    args = parser.parse_args(argv)
    if args.cmd == "serve":
        return _serve_main(args)
    return _drive_main(args)


if __name__ == "__main__":
    import sys

    sys.exit(main())
