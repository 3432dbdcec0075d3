"""Distributed tracing plane: causal spans across hops + batched tick spans.

The runtime's three observability surfaces — counters (stats.py), the
telemetry fan-out (telemetry.py), and throttled structured logs
(tracing.py) — answer *how much* and *what happened*, but not *which
hops did THIS request take and where did its latency go*.  This module
is the causal thread between them, the Dapper model (Sigelman et al.,
2010) adapted to the TPU-first runtime:

* a **trace context** ``{"trace_id", "span_id", "sampled"}`` is generated
  at client/gateway ingress and rides the existing ``RequestContext``
  export that already travels with every message
  (runtime/messaging.py: ``Message.request_context``) under the reserved
  key ``TRACE_KEY`` — no new wire field, no codec change;
* **hop spans** open/close at each hop: client send, gateway
  ingress/forward, dispatch queue wait, activation turn, transient
  resend, cross-silo forward, and storage/provider calls as dependency
  spans;
* **engine ticks get BATCHED spans** — one span per tick annotated with
  batch size, per-(type, method) message counts and compile events,
  never one span per message (per-message device spans would serialize
  the kernels; see the TPU-first note in stats.py).  A tick span becomes
  the shared child of every request it executed via link events, so a
  request's critical path is attributable to a specific compile or an
  oversized batch;
* **head-based sampling** decides at ingress whether a trace's OK spans
  are retained (``TracingConfig.sample_rate``); spans that end in an
  error, a timeout, or any dead-letter drop are recorded ALWAYS — the
  ids propagate regardless of sampling exactly so the failure path can
  be reconstructed;
* a bounded per-silo **flight recorder** ring keeps the most recent
  completed spans; ``dump()`` correlates them with dead letters (which
  carry the trace id, resilience.DeadLetterRing) and recent
  circuit-breaker transitions — the crash-evidence bundle emitted when a
  chaos invariant fails or ``silo.snapshot()`` reports degraded.

Everything here is host-path bookkeeping: plain dataclasses and deques,
zero device work.  With ``TracingConfig.enabled=False`` every entry
point returns before allocating anything.  These spans live on the
host clock only; the spans that reach the device trace, on its clock,
are the profiler's stages (``tensor/profiler.Stage``), and a tick
span's ``phases`` are folded from those stages' totals.
"""

from __future__ import annotations

import random
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional

from orleans_tpu.core.context import RequestContext
from orleans_tpu.resilience import (
    DEAD_LETTER_REASONS,
    REASON_BREAKER_OPEN,
    REASON_EXPIRED,
    REASON_MAILBOX_OVERFLOW,
    REASON_RETRY_BUDGET,
    REASON_SHED,
    REASON_UNDELIVERABLE,
    TRACE_CONTEXT_KEY,
)

#: reserved RequestContext key the trace context rides under (shared
#: literal lives in resilience.py so the dead-letter ring can extract
#: trace ids without importing this module)
TRACE_KEY = TRACE_CONTEXT_KEY

# ---- span statuses --------------------------------------------------------

STATUS_OK = "ok"
STATUS_ERROR = "error"
STATUS_TIMEOUT = "timeout"
STATUS_REJECTED = "rejected"

#: every dead-letter reason code maps to a span status — the third ledger
#: next to the SiloMetrics counter and the DeadLetterRing record (the
#: tests/test_tracing_spans.py lint asserts the three stay in sync)
DEAD_LETTER_SPAN_STATUS: Dict[str, str] = {
    REASON_EXPIRED: "dropped_expired",
    REASON_SHED: "dropped_shed",
    REASON_MAILBOX_OVERFLOW: "dropped_mailbox_overflow",
    REASON_BREAKER_OPEN: "dropped_breaker_open",
    REASON_RETRY_BUDGET: "dropped_retry_budget",
    REASON_UNDELIVERABLE: "dropped_undeliverable",
}
assert set(DEAD_LETTER_SPAN_STATUS) == set(DEAD_LETTER_REASONS)


_id_rng = random.Random()
_getrandbits = _id_rng.getrandbits


def new_id() -> int:
    """63-bit span/trace id (Dapper-style; uniqueness, not crypto).  An
    int, not hex text: ids are minted once per request on the hot path
    and formatting them would cost more than generating them — they
    serialize fine as JSON numbers and compare by equality everywhere."""
    return _getrandbits(63)


# ---- trace context helpers ------------------------------------------------

from orleans_tpu.core.context import _request_context  # noqa: E402


def current_trace() -> Optional[Dict[str, Any]]:
    """The ambient trace context of the executing task, if any."""
    rc = _request_context.get()
    if rc is None:
        return None
    t = rc.get(TRACE_KEY)
    return t if isinstance(t, dict) else None


def trace_of(msg: Any) -> Optional[Dict[str, Any]]:
    """The trace context carried by a message's exported RequestContext."""
    rc = getattr(msg, "request_context", None)
    if not isinstance(rc, dict):
        return None
    t = rc.get(TRACE_KEY)
    return t if isinstance(t, dict) else None


def trace_id_of(msg: Any) -> Optional[str]:
    t = trace_of(msg)
    return t.get("trace_id") if t else None


# ---- the span record ------------------------------------------------------

@dataclass
class Span:
    """One completed (or in-flight) hop of one request — or one engine
    tick (``trace_id == ""``: tick spans are shared by every request the
    tick executed and join traces through link events instead)."""

    trace_id: Any                    # int id; "" for tick spans
    span_id: Any
    parent_id: Optional[Any]
    name: str
    kind: str
    silo: str
    sampled: bool
    start: float                     # time.monotonic()
    duration: float = 0.0
    status: str = STATUS_OK
    attrs: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "kind": self.kind,
            "silo": self.silo,
            "sampled": self.sampled,
            "start": round(self.start, 6),
            "duration_s": round(self.duration, 6),
            "status": self.status,
            "attrs": {k: (v if isinstance(v, (int, float, bool, str,
                                              type(None))) else str(v))
                      for k, v in self.attrs.items()},
        }


# ---- flight recorder ------------------------------------------------------

class FlightRecorder:
    """Bounded ring of recent completed spans — the per-silo crash
    evidence.  ``dump()`` correlates the retained spans by trace id with
    the dead-letter entries (which carry trace ids) and recent breaker
    transitions handed in by the caller."""

    def __init__(self, capacity: int = 256) -> None:
        self.capacity = capacity
        self.spans: deque = deque(maxlen=capacity)
        self.dropped = 0          # spans evicted by the ring bound
        self.dumps = 0

    def add(self, span: Span) -> None:
        if len(self.spans) == self.spans.maxlen:
            self.dropped += 1
        self.spans.append(span)

    def resize(self, capacity: int) -> None:
        if capacity == self.capacity:
            return
        self.capacity = capacity
        self.spans = deque(self.spans, maxlen=capacity)

    def dump(self, reason: str = "",
             dead_letters: Optional[Iterable[Dict[str, Any]]] = None,
             breaker_transitions: Optional[Iterable[Dict[str, Any]]] = None,
             collection_slices: Optional[Iterable[Dict[str, Any]]] = None,
             profile_captures: Optional[Iterable[Dict[str, Any]]] = None
             ) -> Dict[str, Any]:
        """The correlated evidence bundle: spans grouped by trace, each
        trace joined with its dead letters; tick spans and unattributable
        dead letters reported alongside (bounded)."""
        self.dumps += 1
        spans = [s.to_dict() for s in self.spans]
        traces: Dict[str, Dict[str, List[Any]]] = {}
        untraced: List[Dict[str, Any]] = []
        for sp in spans:
            tid = sp["trace_id"]
            if tid:
                traces.setdefault(tid, {"spans": [], "dead_letters": []})[
                    "spans"].append(sp)
            else:
                untraced.append(sp)
        orphans: List[Dict[str, Any]] = []
        for entry in list(dead_letters or []):
            tid = entry.get("trace_id")
            if tid and tid in traces:
                traces[tid]["dead_letters"].append(entry)
            else:
                orphans.append(entry)
        return {
            "reason": reason,
            "captured_spans": len(spans),
            "ring_dropped": self.dropped,
            "traces": traces,
            "untraced_spans": untraced[-32:],
            "dead_letters_untraced": orphans[-32:],
            "breaker_transitions": list(breaker_transitions or []),
            # recent incremental-collection slices (engine.collect):
            # a crash mid-sweep names what the collector was doing
            "collection_slices": list(collection_slices or [])[-32:],
            # jax.profiler deep captures (tensor/profiler.py): a latency
            # incident that breached the capture threshold ships with
            # the trace-directory reference to its own profile
            "profile_captures": list(profile_captures or [])[-8:],
        }


# ---- the timeline log -----------------------------------------------------

class TimelineRecorder:
    """Bounded per-silo timeline: completed spans + interval metric
    deltas + lifecycle events, appended in arrival order on the silo's
    OWN monotonic clock.  A collector (testing/cluster.py in-process,
    orleans_tpu/timeline.py file-handoff for the multiprocess runner)
    merges the per-silo exports onto one reference clock using the
    gossip-piggybacked offset estimates recorded here, and renders
    ``TIMELINE.json`` plus a Chrome trace-event (Perfetto) export.

    Everything is host bookkeeping on one deque; with ``enabled=False``
    every entry point returns before allocating."""

    def __init__(self, silo: str, capacity: int = 4096,
                 enabled: bool = True) -> None:
        self.silo = silo
        self.enabled = enabled
        self.capacity = capacity
        self.events: deque = deque(maxlen=capacity)
        self.appended = 0
        self.dropped = 0          # events evicted by the ring bound
        # peer → best (lowest-RTT) offset estimate: REMOTE monotonic
        # minus LOCAL monotonic, half-RTT corrected
        self.clock_offsets: Dict[str, Dict[str, float]] = {}

    def _append(self, record: Dict[str, Any]) -> None:
        if len(self.events) == self.events.maxlen:
            self.dropped += 1
        self.appended += 1
        self.events.append(record)

    def resize(self, capacity: int) -> None:
        if capacity == self.capacity:
            return
        self.capacity = capacity
        self.events = deque(self.events, maxlen=capacity)

    # -- appenders ----------------------------------------------------------

    def record_span(self, span: "Span") -> None:
        if self.enabled:
            self._append({"kind": "span", **span.to_dict()})

    def lifecycle(self, event: str, **attrs: Any) -> None:
        """join/drain/kill/promote/ring-change — the cluster's phase
        boundaries; always cheap enough to record unconditionally."""
        if self.enabled:
            self._append({"kind": "lifecycle", "event": event,
                          "silo": self.silo,
                          "start": round(time.monotonic(), 6),
                          "attrs": {k: (v if isinstance(
                              v, (int, float, bool, str, type(None)))
                              else str(v)) for k, v in attrs.items()}})

    def metrics_delta(self, delta: Dict[str, float]) -> None:
        """One interval's counter deltas (collect_metrics cadence) —
        the timeline's load context between spans."""
        if self.enabled and delta:
            self._append({"kind": "metrics",
                          "start": round(time.monotonic(), 6),
                          "delta": {k: round(float(v), 6)
                                    for k, v in delta.items()}})

    # -- clock merge --------------------------------------------------------

    def note_clock_offset(self, peer: str, offset_s: float,
                          rtt_s: float) -> None:
        """One probe's offset sample (remote monotonic − local, half-RTT
        corrected).  The LOWEST-RTT sample wins (NTP's discipline: RTT
        bounds the estimate's error), with a slow decay so a genuinely
        drifted clock eventually re-measures."""
        cur = self.clock_offsets.get(peer)
        if cur is None or rtt_s <= cur["rtt_s"] * 1.5:
            self.clock_offsets[peer] = {
                "offset_s": round(offset_s, 6),
                "rtt_s": round(rtt_s, 6),
                "at": round(time.monotonic(), 6)}

    def worst_clock_offset_s(self) -> float:
        """Largest absolute peer-offset estimate; ``-1.0`` when no peer
        has been probed yet (the dashboard's no-data sentinel — an
        empty estimate table must never read as 'perfectly synced')."""
        if not self.clock_offsets:
            return -1.0
        return max(abs(o["offset_s"]) for o in self.clock_offsets.values())

    # -- export -------------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        return {
            "enabled": self.enabled,
            "capacity": self.capacity,
            "backlog": len(self.events),
            "appended": self.appended,
            "dropped": self.dropped,
            "peers_probed": len(self.clock_offsets),
            "worst_clock_offset_s": self.worst_clock_offset_s(),
        }

    def export(self) -> Dict[str, Any]:
        """The per-silo handoff payload the collector merges (JSON-safe;
        see orleans_tpu/timeline.py merge_timelines)."""
        return {
            "silo": self.silo,
            "exported_at": round(time.monotonic(), 6),
            "appended": self.appended,
            "dropped": self.dropped,
            "clock_offsets": {p: dict(o)
                              for p, o in self.clock_offsets.items()},
            "events": list(self.events),
        }

    def tail(self, n: int = 64) -> List[Dict[str, Any]]:
        """The newest ``n`` events — the incident bundle's timeline
        context around a trip."""
        if n <= 0:
            return []
        return list(self.events)[-n:]


# ---- the recorder ---------------------------------------------------------

class SpanRecorder:
    """Per-silo (and per-client) span factory + sampling policy + sinks.

    Sinks: the flight-recorder ring always; ``SpanTelemetryConsumer``s on
    the process telemetry manager when any are registered.  The sampling
    seed derives from the owner's name so head-sampling decisions replay
    across runs of the same topology (the chaos plane's determinism
    discipline, resilience.BackoffPolicy gives the same reason).
    """

    def __init__(self, name: str, enabled: bool = True,
                 sample_rate: float = 0.01, flight_capacity: int = 256,
                 breaker_capacity: int = 64,
                 seed: Optional[int] = None) -> None:
        import zlib
        self.name = name
        self.enabled = enabled
        self.sample_rate = sample_rate
        self._rng = random.Random(zlib.crc32(name.encode())
                                  if seed is None else seed)
        self.flight = FlightRecorder(flight_capacity)
        self.breaker_transitions: deque = deque(maxlen=breaker_capacity)
        self.started = 0              # spans opened
        self.recorded = 0             # spans committed to the sinks
        self.discarded_unsampled = 0  # OK spans of unsampled traces
        self.drop_spans = 0           # always-on dead-letter spans
        self.sampled_traces = 0       # head-sampling YES decisions minted
        # the cluster timeline sink (None until the owner attaches one;
        # every committed span also lands on the timeline when set)
        self.timeline: Optional[TimelineRecorder] = None
        # per-plane monotonic sequence numbers: (silo, plane, seq) is the
        # STABLE id of a plane-span episode across exports
        self._plane_seq: Dict[str, int] = {}

    def configure(self, enabled: Optional[bool] = None,
                  sample_rate: Optional[float] = None,
                  flight_capacity: Optional[int] = None,
                  breaker_capacity: Optional[int] = None) -> None:
        """Live-reload surface (silo.update_config re-push)."""
        if enabled is not None:
            self.enabled = enabled
        if sample_rate is not None:
            self.sample_rate = sample_rate
        if flight_capacity is not None:
            self.flight.resize(flight_capacity)
        if breaker_capacity is not None \
                and breaker_capacity != self.breaker_transitions.maxlen:
            self.breaker_transitions = deque(self.breaker_transitions,
                                             maxlen=breaker_capacity)

    # -- trace context ------------------------------------------------------

    def begin_trace(self, force_sample: bool = False
                    ) -> Optional[Dict[str, Any]]:
        """Ingress: mint a trace context with the head-sampling decision
        baked in.  ``span_id`` starts empty (no parent span yet)."""
        if not self.enabled:
            return None
        sampled = bool(force_sample
                       or self._rng.random() < self.sample_rate)
        if sampled:
            self.sampled_traces += 1
        return {"trace_id": _getrandbits(63), "span_id": "",
                "sampled": sampled}

    def ingress(self) -> Optional[Dict[str, Any]]:
        """The ambient trace if one flows with the caller, else a fresh
        ingress trace (this call IS the client/gateway edge).  Inlined —
        this runs once per request on the hot path."""
        if not self.enabled:
            return None
        rc = _request_context.get()
        if rc is not None:
            t = rc.get(TRACE_KEY)
            if t is not None:
                return t
        sampled = self._rng.random() < self.sample_rate
        if sampled:
            self.sampled_traces += 1
        return {"trace_id": _getrandbits(63), "span_id": "",
                "sampled": sampled}

    @staticmethod
    def child_context(trace: Dict[str, Any], span: Optional[Span]
                      ) -> Dict[str, Any]:
        """The context a hop exports downstream: same trace, this hop's
        span as the parent of whatever the receiver opens."""
        return {"trace_id": trace["trace_id"],
                "span_id": span.span_id if span is not None
                else trace.get("span_id", ""),
                "sampled": bool(trace.get("sampled"))}

    def inject(self, request_context: Optional[Dict[str, Any]],
               trace: Dict[str, Any], span: Optional[Span]
               ) -> Dict[str, Any]:
        """Return a request-context dict carrying the hop's trace context
        (the message's existing RequestContext export is the carrier).
        With no open hop span the trace dict forwards as-is (treated
        immutable everywhere) — zero extra allocation on the unsampled
        hot path."""
        ctx = trace if span is None else \
            {"trace_id": trace["trace_id"], "span_id": span.span_id,
             "sampled": True}
        if request_context:
            rc = dict(request_context)
            rc[TRACE_KEY] = ctx
            return rc
        return {TRACE_KEY: ctx}

    # -- hop spans -----------------------------------------------------------

    def start(self, name: str, kind: str,
              trace: Optional[Dict[str, Any]], **attrs: Any
              ) -> Optional[Span]:
        """Open a hop span under ``trace``.  UNSAMPLED traces open
        nothing — that keeps the default-rate hot path at id-propagation
        cost only (the <5% bench budget); a hop of an unsampled trace
        that ends in a failure is recorded retroactively through
        :meth:`close_hop`/:meth:`event`, which record non-OK statuses
        regardless of sampling."""
        if not self.enabled or trace is None or not trace.get("sampled"):
            return None
        self.started += 1
        return Span(trace_id=trace["trace_id"], span_id=new_id(),
                    parent_id=trace.get("span_id") or None,
                    name=name, kind=kind, silo=self.name,
                    sampled=True, start=time.monotonic(), attrs=attrs)

    def close_hop(self, span: Optional[Span], msg: Any, name: str,
                  kind: str, status: str = STATUS_OK, **attrs: Any) -> None:
        """Finish an open hop span — or, when head sampling skipped
        opening one, record a failure event against the message's carried
        trace (OK outcomes of unsampled hops vanish by design; failures
        never do)."""
        if span is not None:
            self.finish(span, status, **attrs)
            return
        if status == STATUS_OK or not self.enabled:
            return
        self.event(name, kind, trace_of(msg), status=status, **attrs)

    def finish(self, span: Optional[Span], status: str = STATUS_OK,
               **attrs: Any) -> None:
        if span is None:
            return
        span.duration = time.monotonic() - span.start
        span.status = status
        if attrs:
            span.attrs.update(attrs)
        self._commit(span)

    def event(self, name: str, kind: str,
              trace: Optional[Dict[str, Any]], start: Optional[float] = None,
              duration: float = 0.0, status: str = STATUS_OK,
              **attrs: Any) -> None:
        """Retroactive/instant span (queue wait, forward, resend, gateway
        hop): nothing is allocated for an unsampled-OK event."""
        if not self.enabled or trace is None:
            return
        if not trace.get("sampled") and status == STATUS_OK:
            return
        self.started += 1
        now = time.monotonic()
        self._commit(Span(
            trace_id=trace["trace_id"], span_id=new_id(),
            parent_id=trace.get("span_id") or None, name=name, kind=kind,
            silo=self.name, sampled=bool(trace.get("sampled")),
            start=start if start is not None else now,
            duration=duration, status=status, attrs=dict(attrs)))

    def drop(self, reason: str, detail: str = "",
             trace_id: Optional[str] = None, method: str = "",
             target: str = "") -> None:
        """Always-on span for a dead-lettered message (wired to the
        DeadLetterRing's on_record fan-out): every terminal drop leaves a
        span with the reason's status, sampled or not."""
        if not self.enabled:
            return
        self.started += 1
        self.drop_spans += 1
        self._commit(Span(
            trace_id=trace_id or "", span_id=new_id(), parent_id=None,
            name=f"drop {method or reason}", kind="drop", silo=self.name,
            sampled=True, start=time.monotonic(), duration=0.0,
            status=DEAD_LETTER_SPAN_STATUS.get(reason, "dropped"),
            attrs={"reason": reason, "detail": detail, "target": target}))

    # -- batched engine-tick spans -------------------------------------------

    def tick_span(self, tick: int, start: float, duration: float,
                  messages: int, rounds: int,
                  per_method: Dict[str, int], compiles: int,
                  traces: List[Dict[str, Any]],
                  phases: Optional[Dict[str, float]] = None,
                  compile_events: Optional[List[Dict[str, Any]]] = None
                  ) -> Span:
        """ONE span for one engine tick (never per-message — the TPU-first
        batching discipline), plus a link event into every distinct
        SAMPLED trace the tick executed (``traces`` carries sampled
        contexts only — the engine filters at enqueue) so a request's
        critical path names its tick (and that tick's compile events /
        batch size).  ``phases`` carries the tick-phase profiler's
        host/h2d/dispatch/route/d2h breakdown; ``compile_events`` the
        cause-coded compiles this tick paid (tensor/profiler.py) — a
        slow tick in the flight recorder names its slow phase and its
        compile cause without a reproduction run."""
        self.started += 1
        attrs = {"tick": tick, "messages": messages, "rounds": rounds,
                 "per_method": dict(per_method), "compiles": compiles,
                 "linked_traces": 0}
        if phases:
            attrs["phases"] = {p: round(v, 6) for p, v in phases.items()}
        if compile_events:
            attrs["compile_events"] = [
                {"cause": e["cause"], "key": e["key"],
                 "seconds": e["seconds"]} for e in compile_events]
        span = Span(
            trace_id="", span_id=new_id(), parent_id=None,
            name=f"tick {tick}", kind="engine.tick", silo=self.name,
            sampled=True, start=start, duration=duration,
            attrs=attrs)
        seen: set = set()
        for t in traces:
            tid = t.get("trace_id")
            if not tid or tid in seen:
                continue
            seen.add(tid)
            self.event(f"tick {tick}", "engine.tick.link", t,
                       start=start, duration=duration,
                       tick_span_id=span.span_id, tick=tick,
                       batch_messages=messages, compiles=compiles)
        span.attrs["linked_traces"] = len(seen)
        self._commit(span)
        return span

    def collect_span(self, tick: int, duration: float, evicted: int,
                     remaining: int, sweep_done: bool,
                     failed: bool = False) -> Span:
        """ONE batched span per collection SLICE (engine.collect) — the
        incremental activation collector's pause evidence: how long this
        slice stalled the tick, how many rows it evicted, how much of
        the sweep remains.  Batched like tick spans (never one span per
        evicted row); always recorded so a pause-budget overrun is
        visible in the flight recorder even at sample_rate 0."""
        self.started += 1
        span = Span(
            trace_id="", span_id=new_id(), parent_id=None,
            name=f"collect tick {tick}", kind="engine.collect",
            silo=self.name, sampled=True,
            start=time.monotonic() - duration, duration=duration,
            status=STATUS_ERROR if failed else STATUS_OK,
            attrs={"tick": tick, "evicted": evicted,
                   "remaining": remaining, "sweep_done": sweep_done,
                   "write_back_failed": failed})
        self._commit(span)
        return span

    # -- device-plane interval spans -----------------------------------------

    def plane_span(self, plane: str, name: str,
                   start: Optional[float] = None, duration: float = 0.0,
                   status: str = STATUS_OK, **attrs: Any
                   ) -> Optional[Span]:
        """ONE interval span for one device-plane episode — an exchange
        re-trace, a grant growth step, a stream fan-out tick, a timer
        harvest, a checkpoint pin/drain/seal, a journal segment seal, a
        migration wave, a standby tail/promote, a rebalance decision —
        annotated with the plane's own counters (rows moved, lanes
        sealed, harvest width).  Batched like tick spans: one span per
        EPISODE, never per row.  Always recorded (``trace_id == ""``,
        sampled) so the timeline has every plane's track at sample_rate
        0; the stable identity of an episode across exports is
        ``(silo, plane, seq)`` — seq is a per-plane monotonic counter,
        not a random id."""
        if not self.enabled:
            return None
        seq = self._plane_seq.get(plane, 0) + 1
        self._plane_seq[plane] = seq
        self.started += 1
        span = Span(
            trace_id="", span_id=new_id(), parent_id=None,
            name=name, kind=f"plane.{plane}", silo=self.name,
            sampled=True,
            start=(time.monotonic() - duration) if start is None
            else start,
            duration=duration, status=status,
            attrs={"plane": plane, "seq": seq, **attrs})
        self._commit(span)
        return span

    # -- breaker evidence ----------------------------------------------------

    def note_breaker(self, target: Any, old: str, new: str,
                     reason: str) -> None:
        """Recent breaker transitions ride the flight-recorder dump."""
        self.breaker_transitions.append(
            {"target": str(target), "from": old, "to": new,
             "reason": reason, "time": time.monotonic()})

    # -- sinks ---------------------------------------------------------------

    def _commit(self, span: Span) -> None:
        if not span.sampled and span.status == STATUS_OK:
            self.discarded_unsampled += 1
            return
        self.recorded += 1
        self.flight.add(span)
        tl = self.timeline
        if tl is not None:
            tl.record_span(span)
        from orleans_tpu import telemetry
        mgr = telemetry.default_manager
        if mgr.consumers:
            mgr.track_span(span.to_dict())

    def snapshot(self) -> Dict[str, Any]:
        return {
            "enabled": self.enabled,
            "sample_rate": self.sample_rate,
            "started": self.started,
            "recorded": self.recorded,
            "discarded_unsampled": self.discarded_unsampled,
            "drop_spans": self.drop_spans,
            "sampled_traces": self.sampled_traces,
            "flight_capacity": self.flight.capacity,
            "flight_retained": len(self.flight.spans),
            "flight_dropped": self.flight.dropped,
            "timeline": (self.timeline.snapshot()
                         if self.timeline is not None else None),
        }
