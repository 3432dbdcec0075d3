"""Silo watchdog: health-check participants + event-loop stall detection.

Parity: reference Watchdog — a dedicated thread that periodically (a) asks
each IHealthCheckParticipant whether it is healthy and (b) measures how
late its own timer fired, flagging GC pauses / thread starvation
(reference: src/OrleansRuntime/Silo/Watchdog.cs:32 — CheckYourOwnHealth
clock-drift check, participants wired at Silo.cs:261,366;
IHealthCheckParticipant.cs).

Runtime mapping: the silo is one asyncio event loop, so the reference's
"GC pause" failure mode becomes *event-loop stall* — a turn or callback
hogging the loop delays every timer.  A heartbeat coroutine on the loop
measures its own wake drift exactly like the reference measures timer
drift, and anything beyond the threshold is reported.  A daemon thread
(the reference's watchdog thread) watches that heartbeat: while a stall
is still going on it records what the loop thread is doing — the
innermost profiler stage open there (``tensor/profiler.Stage``) and its
top Python frames — so the report names what blocked.  Participants are
duck-typed: any component with ``check_health() -> bool`` registers.
"""

from __future__ import annotations

import asyncio
import os
import sys
import threading
import time
import traceback
from collections import defaultdict
from typing import Any, Dict, List, Optional

#: loop-thread frames kept with a stall: the innermost ones, plus as
#: many of this package's own innermost frames (a stall deep inside a
#: library still names the program's call into it)
STALL_FRAMES = 6
_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _frames_of(thread_id: int) -> List[str]:
    """The loop thread's kept frames, outermost first."""
    frame = sys._current_frames().get(thread_id)
    if frame is None:
        return []
    stack = traceback.StackSummary.extract(
        traceback.walk_stack(frame), limit=64, lookup_lines=False)
    keep = set(range(min(STALL_FRAMES, len(stack))))
    keep.update([i for i, fs in enumerate(stack)
                 if fs.filename.startswith(_PACKAGE)][:STALL_FRAMES])
    return [f"{os.path.basename(stack[i].filename)}:{stack[i].lineno} "
            f"{stack[i].name}" for i in sorted(keep, reverse=True)]


class Watchdog:
    """(reference: Watchdog.cs:32)"""

    def __init__(self, silo, period: float = 5.0,
                 stall_threshold: float = 1.0) -> None:
        self.silo = silo
        self.period = period
        self.stall_threshold = stall_threshold
        self.participants: List[Any] = []
        self.failed_checks = 0
        self.loop_stalls = 0
        # stall seconds by the innermost stage open when the thread saw
        # the stall ("none" when no stage was open, "unseen" when the
        # stall ended before the thread looked)
        self.stall_seconds_by_stage: Dict[str, float] = defaultdict(float)
        self.last_stall: Optional[Dict[str, Any]] = None
        self.last_check_time: Optional[float] = None
        self._task: Optional[asyncio.Task] = None
        self._running = False
        self._was_failing = False  # health-trip edge-trigger state
        self._beat = 0.0
        # the stall evidence the thread hands the loop, under the lock
        self._evidence: Optional[Dict[str, Any]] = None
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._halt = threading.Event()
        self.logger = silo.logger.child("watchdog")

    def register(self, participant: Any) -> None:
        """(reference: Silo wiring IHealthCheckParticipants :366)"""
        if participant is not None and hasattr(participant, "check_health"):
            self.participants.append(participant)

    def _interval(self) -> float:
        """Heartbeat period: fine enough that a stall of the threshold
        is seen while it lasts, never coarser than the check period."""
        return min(self.period, self.stall_threshold / 2)

    def start(self) -> None:
        self._running = True
        self._beat = time.monotonic()
        self._task = asyncio.get_running_loop().create_task(self._loop())
        self._halt.clear()
        self._thread = threading.Thread(
            target=self._watch, args=(threading.get_ident(),),
            name=f"watchdog-{self.silo.name}", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._running = False
        if self._task is not None:
            self._task.cancel()
            self._task = None
        self._halt.set()
        if self._thread is not None:
            self._thread.join(timeout=1.0)
            self._thread = None

    async def _loop(self) -> None:
        try:
            next_check = time.monotonic() + self.period
            while self._running:
                interval = self._interval()
                beat = self._beat = time.monotonic()
                await asyncio.sleep(interval)
                now = time.monotonic()
                drift = now - beat - interval
                with self._lock:
                    evidence, self._evidence = self._evidence, None
                if evidence is not None and evidence["beat"] != beat:
                    evidence = None  # seen late, after this beat's wake
                if drift > self.stall_threshold:
                    # the loop could not run us on time: something hogged
                    # it (reference: CheckYourOwnHealth clock-drift warn)
                    self._report_stall(drift, evidence)
                if now >= next_check:
                    self.check_participants()
                    next_check = now + self.period
        except asyncio.CancelledError:
            pass

    def _watch(self, loop_thread: int) -> None:
        """The watchdog thread: while the heartbeat is late past the
        threshold, record the loop thread's innermost stage and frames
        (once per stall, the first time it is seen)."""
        from orleans_tpu.tensor.profiler import innermost_stage

        seen = None  # the beat of the last stall recorded
        while not self._halt.wait(self.stall_threshold / 8):
            beat = self._beat
            late = time.monotonic() - beat - self._interval()
            if late > self.stall_threshold and seen != beat:
                seen = beat
                evidence = {"beat": beat,
                            "stage": innermost_stage(loop_thread),
                            "frames": _frames_of(loop_thread)}
                with self._lock:
                    self._evidence = evidence

    def _report_stall(self, drift: float,
                      evidence: Optional[Dict[str, Any]]) -> None:
        if evidence is None:
            stage, frames = "unseen", []
        else:
            stage, frames = evidence["stage"] or "none", evidence["frames"]
        self.loop_stalls += 1
        self.stall_seconds_by_stage[stage] += drift
        self.last_stall = {"seconds": drift, "stage": stage,
                           "frames": frames}
        where = f" in stage {stage}"
        if frames:
            where += f" at {' < '.join(reversed(frames))}"
        self.logger.warn(
            f"event loop stalled {drift:.3f}s past the "
            f"{self._interval():.3f}s watchdog heartbeat{where}",
            code=3001)
        # feed the adaptive admission controller: queue-depth
        # sampling was blind while the loop was wedged, so a
        # stall floors the shed level for a recovery window
        controller = getattr(self.silo, "shed_controller", None)
        if controller is not None:
            controller.note_stall(drift)
        # a stall IS an incident: whatever wedged the loop
        # is in the flight recorder / timeline tail NOW
        bundle = self.silo.incident_bundle(
            f"watchdog: event loop stalled {drift:.3f}s in stage {stage}")
        bundle["stall"] = dict(self.last_stall)

    def check_participants(self) -> int:
        """Run every participant's health check; returns failures this
        round (reference: Watchdog.WatchdogThreadProc participant loop)."""
        failures = 0
        now = time.monotonic()
        for p in self.participants:
            try:
                healthy = p.check_health()
            except Exception:  # noqa: BLE001 — a throwing check IS a failure
                healthy = False
            if not healthy:
                failures += 1
                self.failed_checks += 1
                self.logger.warn(
                    f"health check failed: {type(p).__name__}", code=3002)
        # edge-triggered incident dump: the FIRST round with a failing
        # participant captures the evidence; a participant that stays
        # unhealthy must not re-dump every period
        if failures and not self._was_failing:
            self.silo.incident_bundle(
                f"watchdog: {failures} health check(s) failed")
        self._was_failing = bool(failures)
        self.last_check_time = now
        return failures
