"""Tick-phase profiler + compile-churn attribution: where the time and
the compiles go.

PR 4's spans say *what* happened and PR 6's latency ledger says *how
long* it took; this module is the third leg — *where the cost lives* —
so every budget the next perf arc must attack (cross-shard routing, the
~110ms floor, the stream plane) starts from an attributed number instead
of a guess.  Always-on and cheap, in the spirit of Google-Wide Profiling
(Ren et al., CACM 2010): cost attribution is a permanent plane, not an
ad-hoc debugging session.

Four pieces:

* **Stage** — the one boundary marker.  Entering a stage adds its
  seconds to that stage's totals, opens a ``jax.profiler``
  ``TraceAnnotation`` of its fixed name while a profiler session is
  active (the harness's ``start_trace`` or ``capture()`` below), so the
  host spans share the device trace's clock, and records it as the
  innermost stage open on its thread, which the silo watchdog reads
  mid-stall.  The spans:

  - ``orleans.tick``: one engine tick (``TensorEngine.run_tick``);
  - ``orleans.tick.<stage>``: a stage of the tick, its phase per
    ``STAGE_TO_PHASE`` below;
  - ``orleans.sync.<site>``: a blocking device read (:func:`host_read`);
  - ``orleans.gateway.decode``, ``.submit``, ``.reply``: a gateway
    frame's loop-thread work (``runtime/gateway.GatewayAcceptor``);
  - ``orleans.metrics.collect``: ``Silo.collect_metrics``.

* **TickPhaseProfiler** — splits every engine tick into the canonical
  phases (``host`` bookkeeping, ``h2d`` injection/resolve, ``exchange``
  cross-shard dispatch, ``dispatch`` kernel dispatch, ``route``
  emit/fan-out routing, ``d2h`` write-back) from its per-stage totals
  (the ``tick.<stage>`` spans), accumulates per-phase log2
  histograms (the PR 6 bucket scheme, base 1us — mirrored into the
  ``MetricsRegistry`` by ``silo.collect_metrics``) and attaches the
  per-tick breakdown to the batched tick span.  The time not covered by
  a measured stage is the ``host`` remainder, so phase sums reconcile
  with tick wall time *by construction* — the reconciliation test then
  guards against a future double-counted stage, whose sum would overrun.
* **Triggered deep capture** — when a tick's wall time breaches a
  live-reloadable threshold, the NEXT K ticks are captured with
  ``jax.profiler`` into a trace directory; the capture event (path,
  reason, tick) rides the flight-recorder dump so a latency incident
  ships with its own profile.  ``silo.capture_profile(ticks=N)`` is the
  explicit management entry point.
* **CompileTracker** — every tracked retrace/compile records a CAUSE
  code (the churn cause list below) plus its lowering wall time, into a
  cause-coded counter family and a bounded ring of recent compile
  events.  This replaces the bare ``compile_count()`` int as the
  cross-silo health number: "13 compiles" becomes "13 compiles: 9
  new_method, 4 bucket_growth".

``jax.named_scope`` annotations inside the step/fused programs label the
captured HLO (``orleans.dispatch.<Type>.<method>`` etc.) so a deep
capture's timeline names grain methods, not anonymous fusions.  They are
trace-time-only: zero cost after compilation.
"""

from __future__ import annotations

import math
import os
import tempfile
import threading
import time
from collections import defaultdict, deque
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from orleans_tpu.config import ProfilerConfig

# ---------------------------------------------------------------------------
# the stage primitive
# ---------------------------------------------------------------------------

_perf_counter = time.perf_counter
_get_ident = threading.get_ident
_TraceAnnotation = jax.profiler.TraceAnnotation
#: True while a profiler session records host annotations
_tracing = _TraceAnnotation.is_enabled

#: thread ident → the stages open on that thread, outermost first
_open: Dict[int, List["Stage"]] = {}


class Stage:
    """One named boundary, marked once for every sink: ``with`` it (or
    ``open()``/``close()`` around a segment that a block cannot hold).

    Closing adds the seconds to ``totals[key]`` (when ``totals`` is
    given) and leaves them on ``seconds``.  While a profiler session is
    active the stage is also a ``TraceAnnotation`` of ``name``; with
    none, the stage costs two clock reads and a list push and pop.
    Closing a stage first closes any inner stage an exception left
    open, so the thread's open-stage stack cannot drift."""

    __slots__ = ("name", "totals", "key", "seconds", "_t0", "_ann",
                 "_stack", "_depth")

    def __init__(self, name: str, totals: Optional[Dict[str, float]] = None,
                 key: Optional[str] = None) -> None:
        self.name = name
        self.totals = totals
        self.key = name if key is None else key
        self.seconds = 0.0
        self._stack: Optional[List[Stage]] = None

    def open(self) -> "Stage":
        tid = _get_ident()
        stack = _open.get(tid)
        if stack is None:
            stack = _open[tid] = []
        self._stack = stack
        self._depth = len(stack)
        stack.append(self)
        if _tracing():
            self._ann = _TraceAnnotation(self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = _perf_counter()
        return self

    def close(self) -> float:
        stack = self._stack
        if stack is None:
            return 0.0  # not open
        self._stack = None
        # inner stages close first, so this stage's seconds hold theirs
        for inner in reversed(stack[self._depth + 1:]):
            inner.close()
        dt = _perf_counter() - self._t0
        del stack[self._depth:]
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self.seconds = dt
        if self.totals is not None:
            self.totals[self.key] += dt
        return dt

    __enter__ = open

    def __exit__(self, *exc) -> None:
        self.close()


def innermost_stage(thread_id: int) -> Optional[str]:
    """The name of the innermost stage open on ``thread_id`` (any
    thread may ask), or None."""
    stack = _open.get(thread_id)
    try:
        return stack[-1].name if stack else None
    except IndexError:  # popped between the test and the read
        return None


# ---------------------------------------------------------------------------
# blocking device reads
# ---------------------------------------------------------------------------

#: every site that blocks the event-loop thread on a device read
SYNC_SITES = (
    "miss_checks",       # engine: parked miss-check counts and miss keys
    "fanout_drops",      # engine: parked fan-out drop counts
    "exchange_stats",    # engine: parked cross-shard exchange stats
    "results",           # engine: result delivery to a caller's future
    "ledger",            # latency ledger snapshot
    "attribution",       # attribution snapshot and its gathers
    "attribution_evict",  # attribution: evicted rows' counts retired
    "arena",             # arena row and use-clock reads
    "checkpoint",        # journal seals and snapshot chunk drains
)
_SYNC_NAMES = {s: "orleans.sync." + s for s in SYNC_SITES}
#: per site: seconds blocked and reads made, since process start (per
#: process, like the loop thread every silo in the process shares)
sync_seconds: Dict[str, float] = defaultdict(float)
sync_calls: Dict[str, int] = defaultdict(int)


def host_read(site: str, x: Any) -> Any:
    """``jax.device_get(x)`` inside the ``orleans.sync.<site>`` stage:
    the wait for the device and the copy back, counted per site."""
    with Stage(_SYNC_NAMES[site], sync_seconds, site):
        out = jax.device_get(x)
    sync_calls[site] += 1
    return out


def host_reads() -> Dict[str, Dict[str, float]]:
    """Seconds and calls of :func:`host_read`, per site."""
    return {s: {"seconds": sync_seconds[s], "calls": sync_calls[s]}
            for s in SYNC_SITES if sync_calls[s]}


# ---------------------------------------------------------------------------
# phase model
# ---------------------------------------------------------------------------

#: canonical tick phases, in pipeline order.  ``exchange`` is the
#: cross-shard stage (tensor/exchange.py): bucket-by-destination-shard +
#: all_to_all dispatch between resolution and the step kernel.
PHASES = ("host", "h2d", "exchange", "dispatch", "route", "d2h")

#: tick stage (span ``orleans.tick.<stage>``) → canonical phase.  The
#: stages of one tick are disjoint segments inside run_tick, so their
#: sum never exceeds the tick wall time; whatever the stages did not
#: cover is ``host`` bookkeeping (queue plumbing, span accounting,
#: Python overhead).
STAGE_TO_PHASE: Dict[str, str] = {
    "timers": "host",        # due-timer harvest
    "fanout": "host",        # subscription expansion bookkeeping
    "miss_checks": "host",   # optimistic-resolution drain
    "resolve": "h2d",        # coalesce + pad + destination resolution
    "exchange": "exchange",  # cross-shard all_to_all dispatch
    "apply": "dispatch",     # step-program dispatch (kernel)
    "route": "route",        # emit routing / fan-out enqueue
    "results": "d2h",        # explicit result delivery
    "collect": "d2h",        # eviction write-back slice
    "checkpoint": "d2h",     # periodic arena write-back
}
_TICK_NAMES = {k: "orleans.tick." + k for k in STAGE_TO_PHASE}


def _bucket(value: float, base: float, n: int) -> int:
    """The PR 6 log2 bucket (metrics.bucket_index), inlined with
    ``math`` scalars — this runs up to 5x per tick on the hot path."""
    if value < base:
        return 0
    return min(int(math.log2(value / base)) + 1, n - 1)


class TickPhaseProfiler:
    """Per-engine phase accounting + triggered deep capture.

    All accounting is host-side scalar arithmetic: a stage costs two
    clock reads, a dict add and a list push and pop, and the phase fold
    a handful of adds per tick.  Disabled, ``observe_tick`` is never
    called (the engine gates on ``enabled``); the per-stage totals are
    kept either way, since the engine's snapshot reads them."""

    def __init__(self, engine, config: Optional[ProfilerConfig] = None
                 ) -> None:
        self.engine = engine
        self.config = config or ProfilerConfig()
        # per tick stage: cumulative seconds (stages run between ticks,
        # such as a flush's miss-check drain, accrue here directly), and
        # the seconds of the tick in progress, or of the last one
        self.stage_seconds: Dict[str, float] = defaultdict(float)
        self.tick_stages: Dict[str, float] = defaultdict(float)
        self._in_tick = False
        n = self.config.phase_buckets
        self.hist_base = 1e-6
        # per-phase cumulative seconds + log2 bucket counts (base 1us —
        # the shared PR 6 octave scheme, so the registry mirror and the
        # device latency ledger quantile identically)
        self.phase_seconds: Dict[str, float] = {p: 0.0 for p in PHASES}
        self.phase_counts: Dict[str, np.ndarray] = {
            p: np.zeros(n, dtype=np.int64) for p in PHASES}
        self.last_tick_phases: Dict[str, float] = {}
        self.ticks_observed = 0
        # reconciliation health: ticks whose stage sum OVERRAN the
        # measured wall time by >10% (double-counted stage — a bug the
        # reconciliation test pins)
        self.overrun_ticks = 0
        # pipelined-tick reconciliation credit: device time that ran
        # CONCURRENTLY with later host work (engine.TickPipeline
        # completion events).  Pipelined phases overlap, so per-tick
        # host-side phase sums no longer tile total engine time — the
        # credit is the honest difference, not an accounting error.
        self.overlap_credit_s = 0.0
        # -- deep capture state ------------------------------------------
        self.captures_started = 0
        self.capture_events: deque = deque(maxlen=16)
        self._capture_armed: Optional[Dict[str, Any]] = None
        self._capture_remaining = 0
        self._capture_active: Optional[Dict[str, Any]] = None

    # -- stages --------------------------------------------------------------

    def stage(self, key: str) -> Stage:
        """The ``orleans.tick.<key>`` stage: counted in the tick in
        progress, or between ticks straight into the cumulative
        totals."""
        return Stage(_TICK_NAMES[key],
                     self.tick_stages if self._in_tick
                     else self.stage_seconds, key)

    def tick(self) -> Stage:
        """The ``orleans.tick`` stage around one tick: opening it starts
        a fresh per-tick breakdown, closing it folds that breakdown into
        the cumulative totals.  Its ``seconds`` is the tick's wall."""
        return _TickStage(self)

    # -- configuration -------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self.config.enabled

    def configure(self, **changes: Any) -> None:
        """Live-reload surface (silo.update_config re-push).  A
        phase_buckets change recreates the count arrays (cumulative
        counts reset, same contract as the latency ledger)."""
        for k, v in changes.items():
            if v is not None and hasattr(self.config, k):
                setattr(self.config, k, v)
        n = self.config.phase_buckets
        if len(next(iter(self.phase_counts.values()))) != n:
            self.phase_counts = {p: np.zeros(n, dtype=np.int64)
                                 for p in PHASES}

    def reset(self) -> None:
        """Zero the phase accumulation (bench segment boundaries — the
        same contract as ``DeviceLatencyLedger.reset``).  Capture state
        and events survive: a reset must not orphan an active trace."""
        for p in PHASES:
            self.phase_seconds[p] = 0.0
            self.phase_counts[p][:] = 0
        self.last_tick_phases = {}
        self.ticks_observed = 0
        self.overrun_ticks = 0
        self.overlap_credit_s = 0.0

    # -- per-tick accounting -------------------------------------------------

    def observe_tick(self, duration: float,
                     stages: Dict[str, float],
                     overlap_s: Optional[float] = None) -> Dict[str, float]:
        """Fold one tick's stage timers into the five phases; returns the
        tick's phase breakdown (attached to the batched tick span).  The
        unmeasured remainder accrues to ``host``; a negative remainder
        beyond 10% of the tick (plus the pipeline's ``overlap_s`` credit
        — device work completing under this tick's wall is overlap, not
        double-counting) means a stage was double-counted and is
        surfaced via ``overrun_ticks`` instead of silently clamped.
        ``overlap_s=None`` pulls the credit accrued since the last
        observation from the engine's TickPipeline."""
        if overlap_s is None:
            pipeline = getattr(self.engine, "pipeline", None)
            overlap_s = pipeline.take_tick_overlap() \
                if pipeline is not None else 0.0
        self.overlap_credit_s += overlap_s
        phases = {p: 0.0 for p in PHASES}
        for key, seconds in stages.items():
            phases[STAGE_TO_PHASE.get(key, "host")] += seconds
        remainder = duration - sum(phases.values())
        if remainder >= 0.0:
            phases["host"] += remainder
        elif -remainder > 0.10 * max(duration, 1e-9) + overlap_s:
            self.overrun_ticks += 1
        self.ticks_observed += 1
        base = self.hist_base
        for p, v in phases.items():
            counts = self.phase_counts[p]
            counts[_bucket(v, base, len(counts))] += 1
            self.phase_seconds[p] += v
        self.last_tick_phases = phases
        # triggered deep capture: arm on breach; the capture itself
        # starts at tick end (tick_done) so it covers the NEXT K ticks
        thr = self.config.capture_threshold_s
        if thr > 0.0 and duration > thr and self._capture_active is None \
                and self._capture_armed is None \
                and self.captures_started < self.config.capture_limit:
            # the limit guard lives HERE, not only in _start_capture: a
            # sustained slow phase past the limit must not spam one
            # limit-reached error event per tick and evict the real
            # capture records from the bounded event ring
            self._capture_armed = {
                "reason": f"tick_wall {duration:.4f}s > threshold {thr}s",
                "ticks": self.config.capture_ticks}
        return phases

    def tick_done(self) -> None:
        """End-of-tick capture bookkeeping: count down an active capture
        (stopping at zero or past the wall-clock backstop), then start
        an armed one."""
        if self._capture_active is not None:
            self._capture_remaining -= 1
            if self._capture_remaining <= 0 or time.monotonic() \
                    >= self._capture_active.get("deadline", float("inf")):
                self._stop_capture()
        elif self._capture_armed is not None:
            armed, self._capture_armed = self._capture_armed, None
            # re-check: a live-disable between arming and here must
            # drop the armed capture, not start tracing while the
            # profiler reports disabled
            if self.config.enabled:
                self._start_capture(armed["ticks"], armed["reason"])

    # -- deep capture --------------------------------------------------------

    def capture(self, ticks: int = 8, reason: str = "explicit"
                ) -> Dict[str, Any]:
        """Explicit capture entry point (silo.capture_profile): start a
        jax.profiler trace NOW covering the next ``ticks`` ticks.
        Returns the capture event record (with ``error`` on failure)."""
        if self._capture_active is not None:
            return {"error": "capture already active",
                    **{k: v for k, v in self._capture_active.items()}}
        return self._start_capture(max(1, int(ticks)), reason)

    def _trace_dir(self) -> str:
        root = self.config.capture_dir or os.path.join(
            tempfile.gettempdir(), "orleans_tpu_profiles")
        return os.path.join(
            root, f"capture-{self.captures_started:03d}"
                  f"-tick{self.engine.tick_number}")

    def _start_capture(self, ticks: int, reason: str) -> Dict[str, Any]:
        event: Dict[str, Any] = {
            "tick": self.engine.tick_number, "reason": reason,
            "ticks": ticks, "path": None, "started_at": time.time()}
        if self.captures_started >= self.config.capture_limit:
            event["error"] = (f"capture limit "
                              f"({self.config.capture_limit}) reached")
            self.capture_events.append(event)
            return event
        path = self._trace_dir()
        try:
            import jax
            os.makedirs(path, exist_ok=True)
            jax.profiler.start_trace(path)
        except Exception as exc:  # noqa: BLE001 — profiling must never
            # kill the tick loop (backend/tooling availability varies)
            event["error"] = f"{type(exc).__name__}: {exc}"
            self.capture_events.append(event)
            return event
        event["path"] = path
        self.captures_started += 1
        self._capture_active = event
        self._capture_remaining = ticks
        # wall-clock backstop: the tick countdown only runs while the
        # engine ticks — an IDLE engine (explicit capture on a quiet
        # silo, burst ending mid-capture) must not leave the
        # process-global jax trace open until the next traffic.  When an
        # event loop is running the deadline fires on its own; sync
        # drivers hit the same deadline at the next tick/shutdown.
        max_s = max(1.0, self.config.capture_max_seconds)
        event["deadline"] = time.monotonic() + max_s
        try:
            import asyncio
            asyncio.get_running_loop().call_later(
                max_s, self._deadline_stop, event)
        except RuntimeError:
            pass  # no loop (sync test drivers): tick/shutdown backstop
        self.capture_events.append(event)
        return event

    def _deadline_stop(self, event: Dict[str, Any]) -> None:
        if self._capture_active is event:
            event["deadline_hit"] = True
            self._stop_capture()

    def _stop_capture(self) -> None:
        event, self._capture_active = self._capture_active, None
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as exc:  # noqa: BLE001 — see _start_capture
            if event is not None:
                event["error"] = f"stop: {type(exc).__name__}: {exc}"
            return
        if event is not None:
            event["completed_tick"] = self.engine.tick_number

    def shutdown(self) -> None:
        """Engine stop: never leave a jax.profiler session dangling."""
        if self._capture_active is not None:
            self._stop_capture()
        self._capture_armed = None

    # -- snapshots -----------------------------------------------------------

    def phase_percentiles(self, ps=(50, 99)) -> Dict[str, Dict[str, float]]:
        from orleans_tpu.metrics import percentile_from_counts
        out: Dict[str, Dict[str, float]] = {}
        for p in PHASES:
            counts = self.phase_counts[p]
            out[p] = {f"p{q}": round(percentile_from_counts(
                counts, q, self.hist_base), 9) for q in ps}
        return out

    def snapshot(self) -> Dict[str, Any]:
        total = sum(self.phase_seconds.values())
        return {
            "enabled": self.enabled,
            "ticks_observed": self.ticks_observed,
            "overrun_ticks": self.overrun_ticks,
            "overlap_credit_s": round(self.overlap_credit_s, 6),
            "phase_seconds": {p: round(v, 6)
                              for p, v in self.phase_seconds.items()},
            "phase_fraction": {p: round(v / total, 4) if total > 0 else 0.0
                               for p, v in self.phase_seconds.items()},
            "phase_percentiles": self.phase_percentiles(),
            "last_tick_phases": {p: round(v, 6)
                                 for p, v in self.last_tick_phases.items()},
            "captures_started": self.captures_started,
            "capture_active": self._capture_active is not None,
            "capture_events": list(self.capture_events),
        }


class _TickStage(Stage):
    __slots__ = ("profiler",)

    def __init__(self, profiler: TickPhaseProfiler) -> None:
        super().__init__("orleans.tick")
        self.profiler = profiler

    def open(self) -> Stage:
        p = self.profiler
        p.tick_stages = defaultdict(float)
        p._in_tick = True
        return Stage.open(self)

    def close(self) -> float:
        dt = Stage.close(self)  # closes any stage left open inside first
        p = self.profiler
        if p._in_tick:
            p._in_tick = False
            for k, v in p.tick_stages.items():
                p.stage_seconds[k] += v
        return dt

    __enter__ = open


# ---------------------------------------------------------------------------
# compile-churn attribution
# ---------------------------------------------------------------------------

#: the churn cause list: every tracked retrace site names ONE of these
#: (tests/test_profiler.py lints the call sites against this tuple)
CAUSE_NEW_METHOD = "new_method"            # first compile of a (type, method)
CAUSE_BUCKET_GROWTH = "bucket_growth"      # host batch crossed a padding rung
CAUSE_SHAPE_CHANGE = "shape_change"        # new device-batch shape
CAUSE_EPOCH_MISMATCH = "epoch_mismatch"    # free-list eviction staled a mirror
CAUSE_GENERATION_REPACK = "generation_repack"  # rows moved (grow/compact)
CAUSE_CONFIG_TOGGLE = "config_toggle"      # ledger/config live-reload re-trace
CAUSE_MESH_RESHARD = "mesh_reshard"        # mesh change dropped compiled steps
CAUSE_NEW_WINDOW = "new_window"            # first build of a fused window
CAUSE_CROSS_SHARD = "cross_shard"          # exchange toggle re-specialized a
#                                            seen (type, method, m) step

COMPILE_CAUSES = (
    CAUSE_NEW_METHOD, CAUSE_BUCKET_GROWTH, CAUSE_SHAPE_CHANGE,
    CAUSE_EPOCH_MISMATCH, CAUSE_GENERATION_REPACK, CAUSE_CONFIG_TOGGLE,
    CAUSE_MESH_RESHARD, CAUSE_NEW_WINDOW, CAUSE_CROSS_SHARD,
)


class CompileTracker:
    """Cause-coded compile/retrace accounting for one engine.

    Tracked sites (the ones ``compile_count()`` already counted, plus
    the fused-window builds it could not see): the unfused step-program
    call in ``engine._run_group`` (first call per input signature pays
    trace+lower+compile synchronously — its wall time IS the lowering
    cost) and the fused re-trace sites (``FusedTickProgram.prepare``,
    ``AutoFuser._engage`` AOT lower+compile).  Shared module-level
    kernels (directory resolve, ledger accumulate) stay outside — their
    compile sets are O(log n) by design and budget-pinned by tests."""

    def __init__(self, capacity: int = 128) -> None:
        self.by_cause: Dict[str, int] = {c: 0 for c in COMPILE_CAUSES}
        self.total = 0
        self.lowering_seconds = 0.0
        self.events: deque = deque(maxlen=capacity)
        # events since the last tick-span drain (bounded: a tick that
        # somehow compiles dozens of programs reports the LAST 32)
        self._tick_events: deque = deque(maxlen=32)

    def record(self, cause: str, key: str = "", seconds: float = 0.0,
               tick: int = 0) -> None:
        if cause not in self.by_cause:
            raise ValueError(f"unknown compile cause {cause!r} "
                             f"(must be one of {COMPILE_CAUSES})")
        self.by_cause[cause] += 1
        self.total += 1
        self.lowering_seconds += seconds
        event = {"tick": tick, "cause": cause, "key": key,
                 "seconds": round(seconds, 6)}
        self.events.append(event)
        self._tick_events.append(event)

    def drain_tick_events(self) -> List[Dict[str, Any]]:
        """Events recorded since the last drain — the engine attaches
        them to the batched tick span."""
        if not self._tick_events:
            return []
        out = list(self._tick_events)
        self._tick_events.clear()
        return out

    def snapshot(self) -> Dict[str, Any]:
        return {
            "total": self.total,
            "lowering_seconds": round(self.lowering_seconds, 4),
            "by_cause": {c: n for c, n in self.by_cause.items() if n},
            "recent": list(self.events)[-16:],
        }
