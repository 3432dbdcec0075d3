"""Device cost plane: tick-phase profiler, compile-churn attribution,
HBM memory ledger, deep capture, perf regression gate.

The CI contracts of ISSUE 7: per-tick phase sums reconcile with measured
tick wall time (within 10%), every tracked retrace site carries a cause
code from the churn cause list, memory-ledger owner bytes equal the live
column bytes exactly (and degrade silently to self-accounting where
``device.memory_stats()`` is absent — the CPU backend these tests run
on), triggered captures reference their trace dirs from the flight
recorder, and the perfgate renders pass/fail/tolerance verdicts.
"""

import json
import re
import warnings
from pathlib import Path

import numpy as np
import pytest

import samples.presence  # noqa: F401 — registers PresenceGrain/GameGrain
from orleans_tpu.config import ProfilerConfig, TensorEngineConfig
from orleans_tpu.tensor import COMPILE_CAUSES, TensorEngine
from orleans_tpu.tensor.profiler import PHASES, STAGE_TO_PHASE

pytestmark = pytest.mark.profile

SRC = Path(__file__).resolve().parent.parent / "orleans_tpu"


def _engine(**over):
    cfg = TensorEngineConfig(auto_fusion_ticks=0, tick_interval=0.0)
    return TensorEngine(config=cfg, **over)


def _payload(keys, t):
    return {"game": (keys % 8).astype(np.int32),
            "score": np.ones(len(keys), np.float32),
            "tick": np.full(len(keys), t, np.int32)}


# ---------------------------------------------------------------------------
# tick-phase profiler
# ---------------------------------------------------------------------------

def test_phase_sums_reconcile_with_tick_wall_time(run):
    async def main():
        engine = _engine()
        keys = np.arange(2000, dtype=np.int64)
        injector = engine.make_injector("PresenceGrain", "heartbeat", keys)
        errs = []
        for t in range(12):
            injector.inject(_payload(keys, t))
            engine.run_tick()
            dt = engine.tick_durations[-1]
            phases = engine.profiler.last_tick_phases
            assert set(phases) == set(PHASES)
            errs.append(abs(sum(phases.values()) - dt) / dt)
        await engine.flush()
        # the remainder accrues to `host` by construction, so the sum
        # matches within float error; the 10% band is the contract that
        # catches a future DOUBLE-counted stage (sum > wall)
        assert max(errs) <= 0.10, errs
        assert engine.profiler.overrun_ticks == 0
        prof = engine.profiler.snapshot()
        # flush() may run extra redelivery ticks — every one is observed
        assert prof["ticks_observed"] == engine.ticks_run
        # cumulative reconciliation too: phase seconds vs tick_seconds
        total = sum(prof["phase_seconds"].values())
        assert abs(total - engine.tick_seconds) \
            <= 0.10 * engine.tick_seconds

    run(main())


def test_stage_map_covers_every_engine_stage_key(run):
    """Every stage key the engine ever writes must map to a phase —
    an unmapped key would silently land in `host` and skew attribution."""
    async def main():
        engine = _engine(store=None)
        keys = np.arange(256, dtype=np.int64)
        engine.send_batch("PresenceGrain", "heartbeat", keys,
                          _payload(keys, 1))
        await engine.flush()
        for key in engine.profiler.stage_seconds:
            assert key in STAGE_TO_PHASE, \
                f"engine stage {key!r} not mapped to a phase"

    run(main())


def _host_events(trace_dir):
    """(line, name, start_ns, end_ns) of every ``orleans.*`` host span
    in the trace the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    path, = Path(trace_dir).glob("plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("orleans."):
                    out.append(((plane.name, i), e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def test_tick_stages_reach_the_trace_nested_under_the_tick(run, tmp_path):
    """Under a jax.profiler session every tick stage is a host span named
    ``orleans.tick.<stage>`` inside its ``orleans.tick``, on the trace's
    clock; the profiler's per-stage totals of a tick add up to no more
    than the tick's wall, which the ``orleans.tick`` span measures."""
    import jax

    async def main():
        engine = _engine()
        keys = np.arange(256, dtype=np.int64)
        fut = engine.send_batch("PresenceGrain", "heartbeat", keys,
                                _payload(keys, 1), want_results=True)
        await engine.flush()  # compiled before the trace
        await fut
        prof = engine.profiler
        walls, sums = [], []
        with jax.profiler.trace(str(tmp_path)):
            for t in range(3):
                fut = engine.send_batch("PresenceGrain", "heartbeat", keys,
                                        _payload(keys, t + 2),
                                        want_results=True)
                engine.run_tick()
                await fut
                walls.append(engine.tick_durations[-1])
                sums.append(sum(prof.tick_stages.values()))
        return walls, sums

    walls, sums = run(main())
    for wall, total in zip(walls, sums):
        assert 0.0 < total <= wall
    events = _host_events(tmp_path)
    ticks = [e for e in events if e[1] == "orleans.tick"]
    stages = [e for e in events if e[1].startswith("orleans.tick.")]
    names = {e[1] for e in stages}
    assert {"orleans.tick.fanout", "orleans.tick.resolve",
            "orleans.tick.apply", "orleans.tick.route",
            "orleans.tick.results", "orleans.tick.checkpoint"} <= names
    assert {n.split(".", 2)[2] for n in names} <= set(STAGE_TO_PHASE)
    assert len(ticks) >= 3
    for line, name, s0, s1 in stages:
        assert any(t[0] == line and t[2] <= s0 and s1 <= t[3]
                   for t in ticks), f"{name} outside every orleans.tick"
    # the traced ticks' spans measure the walls the profiler folded
    spans = sorted(t[3] - t[2] for t in ticks)[-3:]
    for wall in walls:
        assert any(abs(d * 1e-9 - wall) <= 0.1 * wall + 5e-4
                   for d in spans), (wall, spans)


def test_host_read_counts_one_blocking_read_per_site():
    import jax.numpy as jnp

    from orleans_tpu.tensor import profiler as P

    calls0 = {s: P.sync_calls[s] for s in P.SYNC_SITES}
    secs0 = {s: P.sync_seconds[s] for s in P.SYNC_SITES}
    x = jnp.arange(4, dtype=jnp.int32) * 2
    for site in P.SYNC_SITES:
        out = P.host_read(site, {"x": x})
        assert isinstance(out["x"], np.ndarray)
        assert out["x"].tolist() == [0, 2, 4, 6]
    for site in P.SYNC_SITES:
        assert P.sync_calls[site] == calls0[site] + 1
        assert P.sync_seconds[site] > secs0[site]
        assert P.host_reads()[site]["calls"] == P.sync_calls[site]
    with pytest.raises(KeyError):
        P.host_read("not_a_site", x)  # span names are a fixed set


def test_stage_closes_what_an_exception_left_open():
    import threading

    from orleans_tpu.tensor.profiler import Stage, innermost_stage

    me = threading.get_ident()
    totals = {"outer": 0.0, "inner": 0.0}
    outer = Stage("orleans.test.outer", totals, "outer").open()
    Stage("orleans.test.inner", totals, "inner").open()  # never closed
    assert innermost_stage(me) == "orleans.test.inner"
    outer.close()
    assert innermost_stage(me) is None
    assert totals["outer"] >= totals["inner"] > 0.0
    assert outer.close() == 0.0  # a second close counts nothing


def test_phase_histograms_mirror_into_registry(run):
    async def main():
        from orleans_tpu.runtime.silo import Silo

        silo = Silo(name="phase-mirror")
        await silo.start()
        try:
            keys = np.arange(128, dtype=np.int64)
            silo.tensor_engine.send_batch("PresenceGrain", "heartbeat",
                                          keys, _payload(keys, 1))
            await silo.tensor_engine.flush()
            snap = silo.collect_metrics()
            hists = snap["histograms"].get("engine.phase_s", {})
            phases = {lk.split("=", 1)[1] for lk in hists}
            assert phases == set(PHASES)
            ticks = silo.tensor_engine.profiler.ticks_observed
            for h in hists.values():
                assert h["total"] == ticks  # one observation per tick
        finally:
            await silo.stop(graceful=False)

    run(main())


def test_profiler_live_toggle_and_reset(run):
    async def main():
        engine = _engine()
        keys = np.arange(64, dtype=np.int64)
        injector = engine.make_injector("PresenceGrain", "heartbeat", keys)
        injector.inject(_payload(keys, 1))
        engine.run_tick()
        assert engine.profiler.ticks_observed == 1
        engine.profiler.config.enabled = False
        injector.inject(_payload(keys, 2))
        engine.run_tick()
        assert engine.profiler.ticks_observed == 1  # gated off
        engine.profiler.config.enabled = True
        engine.profiler.reset()
        assert engine.profiler.ticks_observed == 0
        assert all(c.sum() == 0
                   for c in engine.profiler.phase_counts.values())
        await engine.flush()

    run(main())


# ---------------------------------------------------------------------------
# compile-churn attribution
# ---------------------------------------------------------------------------

def test_compile_cause_lint_every_record_site_is_cause_coded():
    """Static lint: every `compile_tracker.record(...)` call site in the
    source passes a CAUSE_* literal (resolved against the cause list), so
    no retrace site can ship an ad-hoc cause string."""
    pat = re.compile(r"compile_tracker\.record\(\s*\n?\s*([A-Za-z_]+)")
    sites = 0
    for path in SRC.rglob("*.py"):
        for m in pat.finditer(path.read_text()):
            sites += 1
            name = m.group(1)
            assert name == "cause" or name.startswith("CAUSE_"), \
                f"{path.name}: record() must pass a CAUSE_ literal " \
                f"or a cause variable derived from one, got {name!r}"
    assert sites >= 3  # engine step site + fused prepare + autofuse engage


def test_compile_tracker_rejects_unknown_cause():
    from orleans_tpu.tensor.profiler import CompileTracker

    t = CompileTracker()
    with pytest.raises(ValueError):
        t.record("because_reasons")


def test_compile_causes_new_method_bucket_growth_shape_change(run):
    async def main():
        engine = _engine()
        keys = np.arange(200, dtype=np.int64)
        engine.send_batch("PresenceGrain", "heartbeat", keys,
                          _payload(keys, 1))
        await engine.flush()
        by_cause = dict(engine.compile_tracker.by_cause)
        assert by_cause["new_method"] >= 2  # heartbeat + game fan-in
        # same shapes again: no new compile events
        total0 = engine.compile_tracker.total
        engine.send_batch("PresenceGrain", "heartbeat", keys,
                          _payload(keys, 2))
        await engine.flush()
        assert engine.compile_tracker.total == total0
        # a batch past the next padding rung grows the bucket
        big = np.arange(3000, dtype=np.int64)
        engine.send_batch("PresenceGrain", "heartbeat", big,
                          _payload(big, 3))
        await engine.flush()
        assert engine.compile_tracker.by_cause["bucket_growth"] >= 1
        # every event cause-coded, with lowering wall time attached
        for e in engine.compile_tracker.events:
            assert e["cause"] in COMPILE_CAUSES
            assert e["seconds"] >= 0.0
        assert engine.compile_tracker.lowering_seconds > 0.0

    run(main())


def test_fused_retrace_causes_epoch_config_and_reshard(run):
    async def main():
        engine = _engine()
        keys = np.arange(64, dtype=np.int64)
        # steady-state contract: every emit destination activated before
        # the window freezes its directory mirror
        engine.arena_for("GameGrain").resolve_rows(
            np.arange(8, dtype=np.int64))
        prog = engine.fuse_ticks("PresenceGrain", "heartbeat", keys)
        stacked = {
            "game": np.tile((keys % 8).astype(np.int32), (2, 1)),
            "score": np.tile(np.ones(64, np.float32), (2, 1)),
            "tick": np.tile(np.full(64, 1, np.int32), (2, 1))}
        prog.run(stacked)
        assert prog.verify() == 0
        assert engine.compile_tracker.by_cause["new_window"] == 1
        # free-list eviction (epoch bump, rows stay put) → epoch_mismatch
        arena = engine.arena_for("PresenceGrain")
        extra = np.array([90_000], dtype=np.int64)
        arena.resolve_rows(extra)
        arena.evict_keys(extra, write_back=False)
        prog.run(stacked)
        assert prog.verify() == 0
        assert engine.compile_tracker.by_cause["epoch_mismatch"] == 1
        # live ledger toggle → config_toggle
        engine.ledger.configure(enabled=False)
        prog.run(stacked)
        assert prog.verify() == 0
        assert engine.compile_tracker.by_cause["config_toggle"] == 1
        # reshard: an unfused step signature compiled BEFORE the mesh
        # change recompiles after it — attributed to the reshard, not
        # re-counted as new traffic
        engine.send_batch("PresenceGrain", "heartbeat", keys,
                          _payload(keys, 8))
        await engine.flush()
        assert engine.compile_tracker.by_cause["mesh_reshard"] == 0
        await engine.reshard(None)
        engine.send_batch("PresenceGrain", "heartbeat", keys,
                          _payload(keys, 9))
        await engine.flush()
        assert engine.compile_tracker.by_cause["mesh_reshard"] >= 1
        # tick spans carry the attribution (snapshot section too)
        snap = engine.snapshot()
        assert snap["compile_attribution"]["total"] \
            == engine.compile_tracker.total
        assert set(snap["compile_attribution"]["by_cause"]) \
            <= set(COMPILE_CAUSES)

    run(main())


def test_arena_grow_retraces_are_attributed_generation_repack(run):
    """An arena grow changes every state column's shape, so jax retraces
    EVERY already-seen batch shape — those retraces must be recorded
    (cause generation_repack), not silently skipped because the batch
    shape was seen before (review finding: the signature proxy must
    track the capacity the columns are shaped by)."""
    async def main():
        engine = _engine(initial_capacity=256)
        keys = np.arange(100, dtype=np.int64)
        engine.send_batch("PresenceGrain", "heartbeat", keys,
                          _payload(keys, 1))
        await engine.flush()
        base_events = engine.compile_tracker.total
        arena = engine.arena_for("PresenceGrain")
        cap0 = arena.capacity
        # force growth well past the current capacity, then resend the
        # SAME batch shape: same padding rung, new column shapes
        arena.reserve(4 * cap0)
        assert arena.capacity > cap0
        engine.send_batch("PresenceGrain", "heartbeat", keys,
                          _payload(keys, 2))
        await engine.flush()
        assert engine.compile_tracker.total > base_events
        assert engine.compile_tracker.by_cause["generation_repack"] >= 1

    run(main())


def test_live_disable_drops_armed_capture(run, tmp_path):
    """A capture armed by a threshold breach must NOT start if the
    profiler was live-disabled before tick end (review finding — the
    mirror image of the countdown fix)."""
    async def main():
        engine = _engine(profiler=ProfilerConfig(
            capture_threshold_s=1e-9, capture_ticks=2,
            capture_dir=str(tmp_path)))
        keys = np.arange(32, dtype=np.int64)
        injector = engine.make_injector("PresenceGrain", "heartbeat", keys)
        injector.inject(_payload(keys, 1))
        # breach + disable within the same tick window: observe_tick
        # arms, the live-disable lands before tick_done fires
        prof = engine.profiler
        orig = prof.observe_tick

        def observe_and_arm(duration, stages):
            out = orig(duration, stages)   # arms (every tick breaches)
            prof.config.enabled = False    # live-disable before tick end
            return out

        prof.observe_tick = observe_and_arm
        engine.run_tick()
        assert prof._capture_armed is None
        assert prof._capture_active is None
        assert prof.captures_started == 0
        prof.observe_tick = orig
        await engine.flush()
        engine.profiler.shutdown()

    run(main())


def test_tick_span_carries_phases_and_compile_events(run):
    async def main():
        from orleans_tpu.runtime.silo import Silo
        from orleans_tpu.config import SiloConfig

        cfg = SiloConfig(name="span-phase")
        cfg.tracing.sample_rate = 1.0
        silo = Silo(config=cfg)
        await silo.start()
        try:
            keys = np.arange(100, dtype=np.int64)
            silo.tensor_engine.send_batch("PresenceGrain", "heartbeat",
                                          keys, _payload(keys, 1))
            await silo.tensor_engine.flush()
            ticks = [s for s in silo.spans.flight.spans
                     if s.kind == "engine.tick"]
            assert ticks
            first = ticks[0]
            assert "phases" in first.attrs
            assert set(first.attrs["phases"]) == set(PHASES)
            # the first tick compiled the step programs: the span names
            # the cause-coded events
            assert any("compile_events" in s.attrs for s in ticks)
        finally:
            await silo.stop(graceful=False)

    run(main())


# ---------------------------------------------------------------------------
# memory ledger
# ---------------------------------------------------------------------------

def test_memory_ledger_arena_bytes_exact(run):
    async def main():
        engine = _engine()
        keys = np.arange(4096, dtype=np.int64)
        engine.arena_for("PresenceGrain").reserve(len(keys))
        engine.send_batch("PresenceGrain", "heartbeat", keys,
                          _payload(keys, 1))
        await engine.flush()
        snap = engine.memledger.snapshot()
        for name, arena in engine.arenas.items():
            detail = snap["arenas"][name]
            expect_state = sum(int(col.nbytes)
                               for col in arena.state.values())
            assert detail["state_bytes"] == expect_state
            assert snap["owners"][f"arena.{name}.state"] == expect_state
            assert detail["clock_bytes"] == int(arena.last_use_dev.nbytes)
            # per-(type, field) detail matches each live column exactly
            for fname, col in arena.state.items():
                assert detail["fields"][fname] == int(col.nbytes)
        assert snap["total_self_bytes"] == sum(snap["owners"].values())
        assert snap["peak_self_bytes"] >= snap["total_self_bytes"]

    run(main())


def test_memory_ledger_slack_and_pending_accounting(run):
    async def main():
        import jax.numpy as jnp

        engine = _engine()
        keys = np.arange(1024, dtype=np.int64)
        engine.send_batch("PresenceGrain", "heartbeat", keys,
                          _payload(keys, 1))
        await engine.flush()
        arena = engine.arena_for("PresenceGrain")
        row_bytes = engine.memledger._row_bytes(arena)
        assert row_bytes == sum(
            np.dtype(f.dtype).itemsize * int(np.prod(f.shape or (1,)))
            for f in arena.info.state_fields.values())
        before = engine.memledger.snapshot()
        assert before["arenas"]["PresenceGrain"]["slack_bytes"] == 0
        arena.evict_keys(keys[:100], write_back=False)
        after = engine.memledger.snapshot()
        assert after["arenas"]["PresenceGrain"]["free_rows"] == 100
        assert after["arenas"]["PresenceGrain"]["slack_bytes"] \
            == 100 * row_bytes
        # a queued device-key batch shows up under pending_batches
        engine.queues[("PresenceGrain", "heartbeat")].append(
            __import__("orleans_tpu.tensor.engine",
                       fromlist=["PendingBatch"]).PendingBatch(
                args={"game": jnp.zeros(64, jnp.int32),
                      "score": jnp.ones(64, jnp.float32),
                      "tick": jnp.zeros(64, jnp.int32)},
                keys_dev=jnp.arange(64, dtype=jnp.int32)))
        pending = engine.memledger.snapshot()
        assert pending["pending"]["batches"] == 1
        assert pending["owners"]["pending_batches"] \
            == 64 * (4 + 4 + 4) + 64 * 4  # three arg leaves + keys_dev
        engine.queues.clear()
        await engine.flush()

    run(main())


def test_memory_ledger_degrades_without_memory_stats(run):
    """CPU backend: device.memory_stats() returns None — the ledger
    self-accounts with NO warnings, headroom is None (no-signal), and
    the shed controller treats None as 'clear the floor'."""
    async def main():
        from orleans_tpu.limits import ShedController

        engine = _engine()
        keys = np.arange(128, dtype=np.int64)
        engine.send_batch("PresenceGrain", "heartbeat", keys,
                          _payload(keys, 1))
        await engine.flush()
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            snap = engine.memledger.snapshot()
            head = engine.memledger.headroom()
        assert snap["device"] is None
        assert snap["headroom"] is None
        assert snap["source"] == "self"
        assert head is None
        assert snap["total_self_bytes"] > 0
        sc = ShedController(enabled=True, queue_soft=10, queue_hard=20)
        sc.note_memory_headroom(0.05)   # below watermark → floor
        assert sc.level >= 0.5
        sc.note_memory_headroom(None)   # no-signal → floor clears
        assert sc.level == 0.0
        sc.note_memory_headroom(0.9)    # healthy → stays clear
        assert sc.level == 0.0

    run(main())


def test_silo_emits_memory_gauges_and_feeds_shed_controller(run):
    async def main():
        from orleans_tpu.runtime.silo import Silo

        silo = Silo(name="mem-gauges")
        await silo.start()
        try:
            keys = np.arange(256, dtype=np.int64)
            silo.tensor_engine.send_batch("PresenceGrain", "heartbeat",
                                          keys, _payload(keys, 1))
            await silo.tensor_engine.flush()
            snap = silo.collect_metrics()
            gauges = snap["gauges"]
            assert gauges["memory.self_bytes"][""]["mem-gauges"] > 0
            owners = {lk.split("=", 1)[1]
                      for lk in gauges["memory.owner_bytes"]}
            assert "arena.PresenceGrain" in owners
            # CPU: no device stats → no headroom gauge, floor stays clear
            assert "memory.headroom" not in gauges
            assert silo.shed_controller.memory_headroom is None
            assert silo.shed_controller.level == 0.0
        finally:
            await silo.stop(graceful=False)

    run(main())


# ---------------------------------------------------------------------------
# triggered deep capture
# ---------------------------------------------------------------------------

def test_triggered_capture_threshold_and_flight_reference(run, tmp_path):
    async def main():
        from orleans_tpu.config import SiloConfig
        from orleans_tpu.runtime.silo import Silo

        cfg = SiloConfig(name="capture")
        cfg.profiler.capture_threshold_s = 1e-9  # every tick breaches
        cfg.profiler.capture_ticks = 2
        cfg.profiler.capture_limit = 1
        cfg.profiler.capture_dir = str(tmp_path)
        silo = Silo(config=cfg)
        await silo.start()
        try:
            engine = silo.tensor_engine
            keys = np.arange(64, dtype=np.int64)
            injector = engine.make_injector("PresenceGrain", "heartbeat",
                                            keys)
            for t in range(4):
                injector.inject(_payload(keys, t))
                engine.run_tick()
            await engine.flush()
            engine.profiler.shutdown()
            events = list(engine.profiler.capture_events)
            done = [e for e in events
                    if e.get("path") and not e.get("error")]
            assert done, events
            assert "completed_tick" in done[0]
            assert Path(done[0]["path"]).exists()
            assert str(tmp_path) in done[0]["path"]
            assert engine.profiler.captures_started == 1  # limit held
            # the flight recorder references the capture
            dump = silo.flight_dump("test")
            assert any(e.get("path") == done[0]["path"]
                       for e in dump["profile_captures"])
        finally:
            await silo.stop(graceful=False)

    run(main())


def test_capture_stops_even_when_profiler_disabled_mid_capture(run,
                                                               tmp_path):
    """A live-disabled profiler must not leave an active jax.profiler
    session recording forever: the per-tick countdown runs
    unconditionally (review finding — the trace would otherwise grow
    until engine.stop())."""
    async def main():
        engine = _engine(profiler=ProfilerConfig(capture_dir=str(tmp_path)))
        keys = np.arange(32, dtype=np.int64)
        injector = engine.make_injector("PresenceGrain", "heartbeat", keys)
        event = engine.profiler.capture(ticks=2, reason="test")
        assert event.get("error") is None
        engine.profiler.config.enabled = False  # live-disable mid-capture
        for t in range(3):
            injector.inject(_payload(keys, t))
            engine.run_tick()
        await engine.flush()
        assert engine.profiler._capture_active is None
        assert "completed_tick" in event
        # a fresh capture can start afterwards (session not wedged)
        e2 = engine.profiler.capture(ticks=1, reason="again")
        assert e2.get("error") is None
        engine.profiler.shutdown()

    run(main())


def test_exhausted_capture_limit_does_not_spam_event_ring(run, tmp_path):
    """Past capture_limit a sustained slow phase must not append one
    limit-reached error per tick and evict the real capture records
    from the bounded event ring (review finding)."""
    async def main():
        engine = _engine(profiler=ProfilerConfig(
            capture_threshold_s=1e-9, capture_ticks=1, capture_limit=1,
            capture_dir=str(tmp_path)))
        keys = np.arange(32, dtype=np.int64)
        injector = engine.make_injector("PresenceGrain", "heartbeat", keys)
        for t in range(24):  # way past the event ring's maxlen
            injector.inject(_payload(keys, t))
            engine.run_tick()
        await engine.flush()
        engine.profiler.shutdown()
        events = list(engine.profiler.capture_events)
        assert engine.profiler.captures_started == 1
        real = [e for e in events if e.get("path")]
        assert real, events  # the genuine record survived
        assert len([e for e in events
                    if "limit" in str(e.get("error", ""))]) == 0

    run(main())


def test_idle_engine_capture_stops_at_wall_clock_deadline(run, tmp_path):
    """An explicit capture on a QUIET engine has no tick countdown to
    stop it — the wall-clock backstop must close the process-global jax
    trace on its own (review finding)."""
    import asyncio

    async def main():
        engine = _engine(profiler=ProfilerConfig(
            capture_dir=str(tmp_path), capture_max_seconds=1.0))
        event = engine.profiler.capture(ticks=100, reason="idle")
        assert event.get("error") is None
        await asyncio.sleep(1.3)  # no ticks run at all
        assert engine.profiler._capture_active is None
        assert event.get("deadline_hit") is True
        # a later capture is not refused with "capture already active"
        e2 = engine.profiler.capture(ticks=1, reason="after")
        assert e2.get("error") is None
        engine.profiler.shutdown()

    run(main())


def test_explicit_capture_profile_management_call(run, tmp_path):
    async def main():
        from orleans_tpu.config import SiloConfig
        from orleans_tpu.runtime.silo import Silo

        cfg = SiloConfig(name="mgmt-capture")
        cfg.profiler.capture_dir = str(tmp_path)
        silo = Silo(config=cfg)
        await silo.start()
        try:
            # through the management surface (SiloControl system target)
            event = await silo.system_rpc(silo.address, "silo_control",
                                          "capture_profile", (2,))
            assert event.get("error") is None, event
            assert event["path"]
            engine = silo.tensor_engine
            keys = np.arange(32, dtype=np.int64)
            injector = engine.make_injector("PresenceGrain", "heartbeat",
                                            keys)
            for t in range(3):
                injector.inject(_payload(keys, t))
                engine.run_tick()
            await engine.flush()
            engine.profiler.shutdown()
            assert Path(event["path"]).exists()
            # double-start is refused, not crashed
            e1 = silo.capture_profile(ticks=1)
            e2 = silo.capture_profile(ticks=1)
            silo.tensor_engine.profiler.shutdown()
            assert e1.get("error") is None
            assert "error" in e2
        finally:
            await silo.stop(graceful=False)

    run(main())


# ---------------------------------------------------------------------------
# perf regression gate
# ---------------------------------------------------------------------------

BASELINE = {
    "source": "unit",
    "metrics": {
        "throughput": {"path": "value", "value": 1000.0,
                       "tolerance": 0.2, "direction": "higher"},
        "p99": {"path": "latency.p99_s", "value": 0.1,
                "tolerance": 0.5, "direction": "lower"},
    },
}


def test_perfgate_pass_fail_and_tolerance_edges():
    from orleans_tpu import perfgate

    ok = perfgate.evaluate(BASELINE, {"value": 990.0,
                                      "latency": {"p99_s": 0.12}})
    assert ok["status"] == "pass" and ok["failed"] == 0

    # exactly on the band edge passes; just past it fails
    edge = perfgate.evaluate(BASELINE, {"value": 800.0,
                                        "latency": {"p99_s": 0.15}})
    assert edge["status"] == "pass"
    fail = perfgate.evaluate(BASELINE, {"value": 799.0,
                                        "latency": {"p99_s": 0.12}})
    assert fail["status"] == "fail"
    assert [r["name"] for r in fail["metrics"]
            if r["status"] == "fail"] == ["throughput"]

    # a lower-is-better regression fails in the other direction, and an
    # IMPROVEMENT (lower latency / higher throughput) never fails
    slow = perfgate.evaluate(BASELINE, {"value": 5000.0,
                                        "latency": {"p99_s": 0.16}})
    assert slow["status"] == "fail"
    better = perfgate.evaluate(BASELINE, {"value": 9999.0,
                                          "latency": {"p99_s": 0.001}})
    assert better["status"] == "pass"


def test_perfgate_missing_metrics_and_strictness():
    from orleans_tpu import perfgate

    v = perfgate.evaluate(BASELINE, {"value": 1000.0})
    assert v["status"] == "pass" and v["missing"] == 1
    strict = perfgate.evaluate(BASELINE, {"value": 1000.0},
                               strict_missing=True)
    assert strict["status"] == "fail"


def test_perfgate_empty_baseline_is_error_not_vacuous_pass(tmp_path):
    """A baseline checking NOTHING (empty/missing 'metrics') must read
    as broken — a silently-unguarding gate is the failure mode the gate
    exists to prevent (review finding)."""
    from orleans_tpu import perfgate

    for bad in ({"metrics": {}}, {"metric": BASELINE["metrics"]}):
        v = perfgate.evaluate(bad, {"value": 1000.0})
        assert v["status"] == "error" and v["checked"] == 0
    base = tmp_path / "empty.json"
    base.write_text(json.dumps({"metrics": {}}))
    art = tmp_path / "BENCH_r09.json"
    art.write_text(json.dumps({"parsed": {"value": 1.0}}))
    rc = perfgate.main(["--baseline", str(base), "--artifact", str(art)])
    assert rc == 2


def test_perfgate_unwraps_driver_artifacts():
    from orleans_tpu import perfgate

    assert perfgate.unwrap_artifact(
        {"parsed": {"value": 1.0}}) == {"value": 1.0}
    # the BENCH_r05 shape: truncated capture, parsed null — unusable,
    # never "no regressions"
    assert perfgate.unwrap_artifact({"parsed": None, "tail": "..."}) is None
    assert perfgate.unwrap_artifact({"value": 1.0}) == {"value": 1.0}
    assert perfgate.unwrap_artifact("junk") is None


def test_perfgate_cli_and_markdown(tmp_path):
    from orleans_tpu import perfgate

    base = tmp_path / "PERF_BASELINE.json"
    base.write_text(json.dumps(BASELINE))
    art = tmp_path / "BENCH_r07.json"
    art.write_text(json.dumps(
        {"parsed": {"value": 950.0, "latency": {"p99_s": 0.11}}}))
    md = tmp_path / "gate.md"
    rc = perfgate.main(["--baseline", str(base), "--artifact", str(art),
                        "--markdown", str(md)])
    assert rc == 0
    text = md.read_text()
    assert "PASS" in text and "throughput" in text

    art.write_text(json.dumps({"parsed": {"value": 10.0}}))
    rc = perfgate.main(["--baseline", str(base), "--artifact", str(art)])
    assert rc == 1

    art.write_text(json.dumps({"parsed": None, "tail": "trunc"}))
    rc = perfgate.main(["--baseline", str(base), "--artifact", str(art)])
    assert rc == 2  # unusable artifact is an error, not a pass

    # a malformed baseline is a clean exit-2 JSON error, never a
    # traceback (review finding)
    base.write_text("{not json")
    art.write_text(json.dumps({"parsed": {"value": 1000.0}}))
    rc = perfgate.main(["--baseline", str(base), "--artifact", str(art)])
    assert rc == 2


def test_repo_baseline_is_valid_and_covers_bench_paths():
    """The checked-in PERF_BASELINE.json parses, every entry is
    well-formed, and an artifact holding each banded path at its
    baseline value passes the gate the profile smoke runs."""
    from orleans_tpu import perfgate

    root = Path(__file__).resolve().parent.parent
    baseline = json.loads((root / "PERF_BASELINE.json").read_text())
    assert baseline["metrics"]
    for name, spec in baseline["metrics"].items():
        assert spec["direction"] in ("higher", "lower"), name
        assert 0.0 < spec["tolerance"] < 1.0, name
        assert spec["value"] > 0, name
    artifact: dict = {}
    for spec in baseline["metrics"].values():
        *parents, leaf = spec["path"].split(".")
        node = artifact
        for key in parents:
            node = node.setdefault(key, {})
        node[leaf] = spec["value"]
    v = perfgate.evaluate(baseline, perfgate.unwrap_artifact(
        {"parsed": artifact}))
    assert v["status"] == "pass" and v["missing"] == 0
