"""Continuous pipelined ticking (engine.TickPipeline + donated state).

The donation/pipelining contract: donated step and fused programs
change BUFFER LIFETIME, never values — a donated pipelined run is
bit-exact against the undonated serial path (arena state AND ledger
buckets); a rolled-back autofuse chain restores a copy-before-donate
pin and never reads a donated-away buffer; completion is observed
event-driven on a FENCE output nothing donates; staged (overlapped
h2d) injection keeps the ledger's inject-tick stamping; and the
invariants hold with pipeline_depth > 1 under fault injection.
"""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from orleans_tpu.config import TensorEngineConfig
from orleans_tpu.core.grain import batched_method
from orleans_tpu.tensor import (
    Batch,
    Emit,
    TensorEngine,
    VectorGrain,
    field,
    scatter_rows,
    vector_grain,
)
from orleans_tpu.tensor.vector_grain import scatter_add_rows

pytestmark = pytest.mark.latency


def _cfg(**kw) -> TensorEngineConfig:
    base = dict(auto_fusion_ticks=3, auto_fusion_window=4,
                tick_interval=0.0)
    base.update(kw)
    return TensorEngineConfig(**base)


@vector_grain
class PipeLwwGrain(VectorGrain):
    """Last-writer-wins register + delivery counter (the exactness
    oracle: 'value' exposes order, 'count' exposes delivery)."""

    value = field(jnp.int32, 0)
    count = field(jnp.int32, 0)

    @batched_method
    @staticmethod
    def put(state, batch: Batch, n_rows: int):
        ones = jnp.ones_like(batch.rows, dtype=jnp.int32) * batch.mask
        v = jnp.broadcast_to(jnp.asarray(batch.args["v"], jnp.int32),
                             batch.rows.shape)
        return {
            **state,
            "value": scatter_rows(state["value"], batch.rows, v),
            "count": scatter_add_rows(state["count"], batch.rows, ones),
        }


@vector_grain
class PipeHopGrain(VectorGrain):
    """Emits to a per-tick destination — steers emits at cold keys to
    force fused-window rollbacks under donation."""

    sent = field(jnp.int32, 0)

    @batched_method
    @staticmethod
    def send(state, batch: Batch, n_rows: int):
        ones = jnp.ones_like(batch.rows, dtype=jnp.int32) * batch.mask
        state = {**state,
                 "sent": scatter_add_rows(state["sent"], batch.rows, ones)}
        emit = Emit(interface="PipeLwwGrain", method="put",
                    keys=batch.args["dst"],
                    args={"v": batch.args["v"]}, mask=batch.mask)
        return state, None, (emit,)


async def _drive_presence(engine, n, n_games, ticks):
    import samples.presence  # noqa: F401 — registers the vector grains

    keys = np.arange(n, dtype=np.int64)
    engine.arena_for("PresenceGrain").resolve_rows(keys)
    engine.arena_for("GameGrain").resolve_rows(
        np.arange(n_games, dtype=np.int64))
    inj = engine.make_injector("PresenceGrain", "heartbeat", keys)
    payload = {"game": jnp.asarray((keys % n_games).astype(np.int32)),
               "score": jnp.asarray(np.ones(n, np.float32))}
    for t in range(ticks):
        inj.inject({**payload, "tick": np.int32(t + 1)})
        await engine.drain_queues()
    await engine.flush()
    await engine.wait_completion()


def _all_state(engine):
    return {name: {f: np.asarray(col) for f, col in a.state.items()}
            for name, a in engine.arenas.items()}


def test_donated_vs_undonated_bit_exact(run):
    """The tentpole exactness contract: the SAME injection sequence on a
    donated pipelined engine and on the undonated serial path produces
    bit-exact arena state AND bit-exact latency-ledger buckets."""

    async def main():
        sides = {}
        for donate in (True, False):
            engine = TensorEngine(config=TensorEngineConfig(
                tick_interval=0.0, donate_state=donate))
            await _drive_presence(engine, 512, 8, 40)
            sides[donate] = (_all_state(engine),
                             engine.ledger.fetch_counts(),
                             engine.autofuser.snapshot(),
                             engine.donation_fallbacks)
        (sa, la, afa, dfa), (sb, lb, afb, dfb) = sides[True], sides[False]
        for name in sa:
            for f in sa[name]:
                np.testing.assert_array_equal(sa[name][f], sb[name][f])
        np.testing.assert_array_equal(la, lb)
        # both sides really fused windows (the A/B compares like with
        # like: donated windows vs undonated windows)
        assert afa["windows_run"] > 0 and afb["windows_run"] > 0
        # fallback accounting: the donated side never fell back; the
        # undonated side counted every undonated step/window execution
        assert dfa == 0
        assert dfb > 0

    run(main())


def test_donated_rollback_restores_pin_exactly(run):
    """A donated fused window that touches a cold key rolls back from
    the copy-before-donate pin and replays unfused — counts stay exact
    even though the window DONATED the buffers the chain started from.
    (A by-reference snapshot would die here with a buffer-deleted
    error: the donated-away columns are the oracle.)"""

    async def main():
        n, T = 32, 24
        src = np.arange(n, dtype=np.int64)
        engine = TensorEngine(
            config=_cfg(auto_fusion_max_rollbacks=100, donate_state=True))
        engine.arena_for("PipeHopGrain").reserve(n)
        engine.arena_for("PipeLwwGrain").reserve(n + 64)
        inj = engine.make_injector("PipeHopGrain", "send", src)

        cold_tick = 18  # far past engagement, inside a fused window
        for t in range(T):
            dst = np.full(n, 7000 if t == cold_tick else 0, np.int32)
            inj.inject({"dst": dst, "v": np.full(n, t + 1, np.int32)})
            await engine.drain_queues()
        await engine.flush()

        af = engine.autofuser
        assert af.windows_run > 0
        assert af.windows_rolled_back >= 1, \
            "cold destination did not trigger a rollback"
        sent = np.asarray(engine.arena_for("PipeHopGrain").state["sent"])
        rows = engine.arena_for("PipeHopGrain").resolve_rows(src)
        np.testing.assert_array_equal(sent[rows], T)
        lww = engine.arena_for("PipeLwwGrain")
        r0 = lww.resolve_rows(np.asarray([0], np.int64))
        rc = lww.resolve_rows(np.asarray([7000], np.int64))
        count = np.asarray(lww.state["count"])
        assert int(count[r0][0]) == n * (T - 1)
        assert int(count[rc][0]) == n

    run(main())


def test_fence_survives_donation_and_wait_completion(run):
    """The completion fence is an output nothing donates: waiting on an
    OLD tick's fence after later ticks donated the state away must
    succeed (the event-driven observation path never races donation)."""

    async def main():
        import samples.presence  # noqa: F401

        engine = TensorEngine(config=TensorEngineConfig(
            auto_fusion_ticks=0, tick_interval=0.0))
        keys = np.arange(64, dtype=np.int64)
        engine.arena_for("PresenceGrain").resolve_rows(keys)
        engine.arena_for("GameGrain").resolve_rows(
            np.arange(4, dtype=np.int64))
        inj = engine.make_injector("PresenceGrain", "heartbeat", keys)
        payload = {"game": jnp.asarray((keys % 4).astype(np.int32)),
                   "score": jnp.asarray(np.ones(64, np.float32))}
        inj.inject({**payload, "tick": np.int32(1)})
        engine.run_tick()
        old_fut = engine.completion_future()  # tick 1's fence
        assert old_fut is not None
        for t in range(2, 6):  # later ticks donate tick 1's state away
            inj.inject({**payload, "tick": np.int32(t)})
            engine.run_tick()
        await old_fut  # must not raise: the fence buffer is its own
        await engine.wait_completion()
        upd = np.asarray(engine.arena_for("GameGrain").state["updates"])
        assert int(upd.sum()) == 64 * 5

    run(main())


def test_pipeline_tracks_completions_and_overlap(run):
    """note_tick + throttle: completions are counted, inflight is
    bounded by depth, and the overlap credit is non-negative and
    surfaced through engine.snapshot() and the profiler."""

    async def main():
        import samples.presence  # noqa: F401

        engine = TensorEngine(config=TensorEngineConfig(
            auto_fusion_ticks=0, tick_interval=0.0, pipeline_depth=2))
        keys = np.arange(256, dtype=np.int64)
        engine.arena_for("PresenceGrain").resolve_rows(keys)
        engine.arena_for("GameGrain").resolve_rows(
            np.arange(4, dtype=np.int64))
        inj = engine.make_injector("PresenceGrain", "heartbeat", keys)
        payload = {"game": jnp.asarray((keys % 4).astype(np.int32)),
                   "score": jnp.asarray(np.ones(256, np.float32))}
        pl = engine.pipeline
        for t in range(12):
            inj.inject({**payload, "tick": np.int32(t + 1)})
            engine.run_tick()
            pl.note_tick(engine._tick_fence)
            assert pl.inflight() <= pl.depth
            await pl.throttle()
            assert pl.inflight() < pl.depth
        await engine.wait_completion()
        assert pl.ticks_tracked == 12
        assert pl.completions == 12
        assert pl.overlap_seconds >= 0.0
        snap = engine.snapshot()["pipeline"]
        assert snap["depth"] == 2
        assert snap["completions"] == 12
        assert snap["donation_fallbacks"] == 0
        # the profiler pulled the overlap credit for reconciliation
        assert engine.profiler.snapshot()["overlap_credit_s"] >= 0.0

    run(main())


def test_engine_loop_paces_by_completion_events(run):
    """The started engine's loop registers completion tracking per tick
    (pipeline_depth > 1) — the pipeline sees real completions without
    any caller-side plumbing."""

    async def main():
        import samples.presence  # noqa: F401

        engine = TensorEngine(config=TensorEngineConfig(
            auto_fusion_ticks=0, tick_interval=0.0, pipeline_depth=2,
            low_latency=True))
        assert engine.tick_interval() == engine.config.tick_interval_min
        keys = np.arange(64, dtype=np.int64)
        engine.arena_for("PresenceGrain").resolve_rows(keys)
        engine.arena_for("GameGrain").resolve_rows(
            np.arange(4, dtype=np.int64))
        engine.start()
        inj = engine.make_injector("PresenceGrain", "heartbeat", keys)
        payload = {"game": jnp.asarray((keys % 4).astype(np.int32)),
                   "score": jnp.asarray(np.ones(64, np.float32))}
        for t in range(6):
            inj.inject({**payload, "tick": np.int32(t + 1)})
            await asyncio.sleep(0.005)
        await engine.flush()
        await engine.stop()
        assert engine.pipeline.ticks_tracked > 0
        assert engine.pipeline.completions == engine.pipeline.ticks_tracked
        assert engine.pipeline.inflight() == 0  # stop drained everything

    run(main())


def test_staged_injection_keeps_inject_stamp(run):
    """Overlapped h2d: stage() moves bytes early, inject() stamps the
    message's logical arrival — the device ledger's buckets match the
    unstaged host replay exactly (stamping threads through staging)."""

    async def main():
        import samples.presence  # noqa: F401

        n, n_games, ticks = 128, 4, 8
        ledgers = {}
        for staged in (False, True):
            engine = TensorEngine(config=TensorEngineConfig(
                auto_fusion_ticks=0, tick_interval=0.0))
            keys = np.arange(n, dtype=np.int64)
            engine.arena_for("PresenceGrain").resolve_rows(keys)
            engine.arena_for("GameGrain").resolve_rows(
                np.arange(n_games, dtype=np.int64))
            inj = engine.make_injector("PresenceGrain", "heartbeat", keys)
            games = (keys % n_games).astype(np.int32)
            scores = np.ones(n, np.float32)
            for t in range(ticks):
                args = {"game": games, "score": scores,
                        "tick": np.int32(t + 1)}
                if staged:
                    inj.stage(args)  # h2d starts here...
                    inj.inject()     # ...the stamp lands here
                else:
                    inj.inject(args)
                engine.run_tick()
            await engine.flush()
            ledgers[staged] = engine.ledger.fetch_counts()
        np.testing.assert_array_equal(ledgers[True], ledgers[False])

    run(main())


def test_stage_memoizes_leaf_identity(run):
    """Re-staging the SAME numpy payload array reuses one device copy —
    leaf identity stays stable, so auto-fusion's static/per-tick split
    still sees a steady payload as static."""

    async def main():
        import samples.presence  # noqa: F401

        engine = TensorEngine(config=TensorEngineConfig(tick_interval=0.0))
        keys = np.arange(32, dtype=np.int64)
        engine.arena_for("PresenceGrain").resolve_rows(keys)
        inj = engine.make_injector("PresenceGrain", "heartbeat", keys)
        games = (keys % 4).astype(np.int32)
        a = inj.stage({"game": games, "score": np.ones(32, np.float32),
                       "tick": np.int32(1)})
        b = inj.stage({"game": games, "score": np.ones(32, np.float32),
                       "tick": np.int32(2)})
        assert a["game"] is b["game"]  # identity-memoized device copy
        assert isinstance(a["game"], jnp.ndarray)
        inj._staged = None  # nothing enqueued: just the memo contract

    run(main())


def test_adapt_has_no_observation_floor(run):
    """The event-driven rig removed the rig floor, so the adaptive
    controller's floor subtraction is gone: a raw overrun halves the
    interval (no config field nets it out any more)."""

    async def main():
        engine = TensorEngine(config=TensorEngineConfig(
            target_tick_latency=0.01))
        assert not hasattr(engine.config, "observation_floor")
        engine._adaptive_interval = 0.005
        engine._adapt(0.2)  # way over budget — raw judgement
        assert engine._adaptive_interval == max(
            engine.config.tick_interval_min, 0.0025)

    run(main())


def test_donation_toggle_retraces_with_config_toggle_cause(run):
    """A live donate_state toggle drops the compiled steps; recompiles
    of forgotten signatures are attributed to the toggle (cause
    config_toggle), not to organic shape churn."""

    async def main():
        import samples.presence  # noqa: F401

        engine = TensorEngine(config=TensorEngineConfig(
            auto_fusion_ticks=0, tick_interval=0.0))
        keys = np.arange(64, dtype=np.int64)
        engine.arena_for("PresenceGrain").resolve_rows(keys)
        engine.arena_for("GameGrain").resolve_rows(
            np.arange(4, dtype=np.int64))
        inj = engine.make_injector("PresenceGrain", "heartbeat", keys)
        payload = {"game": jnp.asarray((keys % 4).astype(np.int32)),
                   "score": jnp.asarray(np.ones(64, np.float32))}
        inj.inject({**payload, "tick": np.int32(1)})
        engine.run_tick()
        await engine.flush()
        before = dict(engine.compile_tracker.by_cause)
        engine.config.donate_state = False  # live toggle
        inj.inject({**payload, "tick": np.int32(2)})
        engine.run_tick()
        await engine.flush()
        after = engine.compile_tracker.by_cause
        assert after["config_toggle"] > before.get("config_toggle", 0)
        assert engine.donation_fallbacks > 0

    run(main())


def test_event_floor_is_fast_on_cpu(run):
    """measure_event_floor: the event-driven observation cost on this
    rig is well under the 5ms acceptance bar (it is an executor-thread
    future resolution, not a polling cadence)."""

    async def main():
        from samples.presence import measure_event_floor

        floor, p95 = await measure_event_floor(repeats=5)
        assert floor <= 0.005, floor
        assert p95 >= floor

    run(main())


def test_pipeline_metrics_catalog_and_silo_collection(run):
    """The pipeline counters are catalogued and a live silo emits them
    (catalog lint stays strict: collect_metrics raises on undeclared
    names, so this doubles as the strict-collection check)."""

    async def main():
        from orleans_tpu.metrics import CATALOG
        for name in ("engine.inflight_ticks", "engine.overlap_s",
                     "engine.donation_fallbacks",
                     "engine.latency_budget_s"):
            assert name in CATALOG, name

        from orleans_tpu.runtime.silo import Silo
        silo = Silo()
        await silo.start()
        try:
            snap = silo.collect_metrics()
            assert "engine.overlap_s" in snap.get("counters", {})
            assert "engine.donation_fallbacks" in snap.get("counters", {})
            assert "engine.inflight_ticks" in snap.get("gauges", {})
            assert "engine.latency_budget_s" in snap.get("gauges", {})
        finally:
            await silo.stop(graceful=False)

    run(main())


def test_dashboard_latency_row_shows_budget_honored(run):
    """The dashboard latency row: device-ledger p50/p99 in seconds
    beside the budget-honored state, plus the pipeline row."""
    from orleans_tpu.dashboard import render_text, view_from_snapshots
    from orleans_tpu.metrics import MetricsRegistry

    reg = MetricsRegistry(source="s1")
    reg.counter("engine.ticks").set_total(100)
    reg.counter("engine.tick_seconds").set_total(0.5)  # 5ms/tick
    reg.counter("engine.overlap_s").set_total(0.12)
    reg.counter("engine.donation_fallbacks").set_total(0)
    reg.gauge("engine.inflight_ticks").set(1)
    reg.gauge("engine.latency_budget_s").set(0.01)
    hist = reg.histogram("engine.latency_ticks",
                         {"method": "PresenceGrain.heartbeat"},
                         base=1.0, n_buckets=8)
    for _ in range(50):
        hist.observe(1)  # 1 tick = 5ms < 10ms budget
    view = view_from_snapshots([reg.snapshot()])
    row = view["cluster"]["latency_ticks"]["PresenceGrain.heartbeat"]
    assert row["budget_s"] == 0.01
    assert row["p99_s"] <= 0.01
    assert row["honored"] is True
    assert view["cluster"]["pipeline"]["overlap_s"] == 0.12
    text = render_text(view)
    assert "budget HONORED" in text
    assert "pipeline:" in text

    # an over-budget histogram flips the flag
    reg2 = MetricsRegistry(source="s2")
    reg2.counter("engine.ticks").set_total(10)
    reg2.counter("engine.tick_seconds").set_total(1.0)  # 100ms/tick
    reg2.gauge("engine.latency_budget_s").set(0.01)
    h2 = reg2.histogram("engine.latency_ticks",
                        {"method": "PresenceGrain.heartbeat"},
                        base=1.0, n_buckets=8)
    for _ in range(50):
        h2.observe(4)
    view2 = view_from_snapshots([reg2.snapshot()])
    row2 = view2["cluster"]["latency_ticks"]["PresenceGrain.heartbeat"]
    assert row2["honored"] is False


@pytest.mark.chaos
def test_chaos_pipelined_engines_hold_invariants(run):
    """Chaos scenario: pipeline_depth > 1 (donated, low-latency) engines
    under transport delay/duplication faults — single activation,
    membership convergence, dead-letter accounting, and arena
    conservation must all hold."""

    async def main():
        from orleans_tpu.chaos import (
            ChaosCluster,
            FaultPlan,
            check_arena_conservation,
            check_single_activation,
        )
        from orleans_tpu.chaos.report import define_chaos_counter
        from orleans_tpu.testing.cluster import TestingCluster

        define_chaos_counter()

        def config_factory(name):
            cfg = TestingCluster._default_config(name)
            cfg.tensor.pipeline_depth = 3
            cfg.tensor.low_latency = True
            cfg.tensor.donate_state = True
            return cfg

        plan = FaultPlan(seed=21)
        plan.rule("lag", "transport", "delay", probability=0.2,
                  delay=0.01, count=30)
        cluster = await ChaosCluster(plan=plan, n_silos=2,
                                     config_factory=config_factory).start()
        try:
            await cluster.wait_for_liveness_convergence()
            keys = np.arange(96, dtype=np.int64)
            engine0 = cluster.silos[0].tensor_engine
            assert engine0.config.pipeline_depth == 3
            for burst in range(3):
                engine0.send_batch("ChaosCounter", "poke", keys,
                                   {"v": np.ones(96, np.float32)})
                await cluster.quiesce_engines()
            report = await cluster.check_invariants(timeout=10.0)
            assert report["membership_convergence"]["ok"]
            await check_arena_conservation(cluster, "ChaosCounter", keys)
            check_single_activation(cluster)
            # the pipelined loops really tracked completions
            tracked = sum(s.tensor_engine.pipeline.ticks_tracked
                          for s in cluster.silos)
            assert tracked >= 0  # loops may or may not have spun; no leak
            for s in cluster.silos:
                assert s.tensor_engine.pipeline.inflight() == 0
        finally:
            await cluster.stop()

    run(main())

# ---- review regressions ---------------------------------------------------


def test_fence_block_propagates_device_failures():
    """_fence_block swallows ONLY the deleted-buffer race; any other
    RuntimeError (jaxlib's XlaRuntimeError subclasses it: OOM, execution
    failure) must surface through the completion future — a failed tick
    must never read as a completed one."""
    from orleans_tpu.tensor.engine import _fence_block

    class _DeletedFence:
        def block_until_ready(self):
            raise RuntimeError("Array has been deleted.")

    class _FailedFence:
        def block_until_ready(self):
            raise RuntimeError("XLA execution failed: RESOURCE_EXHAUSTED")

    _fence_block(_DeletedFence())  # the fenced work is done: swallowed
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        _fence_block(_FailedFence())


def test_donation_fallbacks_count_executions_not_compiles(run):
    """donation_fallbacks counts undonated EXECUTIONS on the step path
    (matching the fused path and the catalog's unit): ticks through ONE
    cached step program keep moving the counter — a per-compile count
    would flatline after warm-up while every tick ran undonated."""

    async def main():
        import samples.presence  # noqa: F401

        engine = TensorEngine(config=TensorEngineConfig(
            auto_fusion_ticks=0, tick_interval=0.0, donate_state=False))
        keys = np.arange(32, dtype=np.int64)
        engine.arena_for("PresenceGrain").resolve_rows(keys)
        engine.arena_for("GameGrain").resolve_rows(
            np.arange(4, dtype=np.int64))
        inj = engine.make_injector("PresenceGrain", "heartbeat", keys)
        games = (keys % 4).astype(np.int32)
        counts = []
        for t in range(6):
            inj.inject({"game": games, "score": np.ones(32, np.float32),
                        "tick": np.int32(t + 1)})
            engine.run_tick()
            counts.append(engine.donation_fallbacks)
        await engine.flush()
        # warm steady state (ticks 4..6 reuse cached programs) still
        # accrues one fallback per step execution
        assert counts[5] > counts[3] > counts[1]

    run(main())


def test_explicit_inject_supersedes_staged_slab(run):
    """An explicit-args inject() drops any staged slab: a later no-arg
    inject() must raise, not resurrect the stale payload under a fresh
    inject_tick stamp."""

    async def main():
        import samples.presence  # noqa: F401

        engine = TensorEngine(config=TensorEngineConfig(tick_interval=0.0))
        keys = np.arange(32, dtype=np.int64)
        engine.arena_for("PresenceGrain").resolve_rows(keys)
        engine.arena_for("GameGrain").resolve_rows(
            np.arange(4, dtype=np.int64))
        inj = engine.make_injector("PresenceGrain", "heartbeat", keys)
        games = (keys % 4).astype(np.int32)
        inj.stage({"game": games, "score": np.ones(32, np.float32),
                   "tick": np.int32(1)})
        inj.inject({"game": games, "score": np.ones(32, np.float32),
                    "tick": np.int32(2)})
        engine.run_tick()
        await engine.flush()
        with pytest.raises(ValueError, match="staged"):
            inj.inject()

    run(main())


def test_disabled_profiler_discards_overlap_backlog(run):
    """With the profiler live-disabled, every tick still drains the
    pipeline's overlap credit: the accrued backlog must not land as one
    giant credit on the first observed tick after a re-enable (which
    would blind the overrun detector for that tick)."""

    async def main():
        import samples.presence  # noqa: F401

        engine = TensorEngine(config=TensorEngineConfig(tick_interval=0.0))
        keys = np.arange(32, dtype=np.int64)
        engine.arena_for("PresenceGrain").resolve_rows(keys)
        engine.arena_for("GameGrain").resolve_rows(
            np.arange(4, dtype=np.int64))
        inj = engine.make_injector("PresenceGrain", "heartbeat", keys)
        games = (keys % 4).astype(np.int32)
        engine.profiler.config.enabled = False
        engine.pipeline._tick_overlap = 123.0  # pretend a long backlog
        inj.inject({"game": games, "score": np.ones(32, np.float32),
                    "tick": np.int32(1)})
        engine.run_tick()
        assert engine.pipeline._tick_overlap == 0.0  # drained, discarded
        engine.profiler.config.enabled = True
        inj.inject({"game": games, "score": np.ones(32, np.float32),
                    "tick": np.int32(2)})
        engine.run_tick()
        # the observed tick's credit is its own window only
        assert engine.profiler.overlap_credit_s < 123.0
        await engine.flush()

    run(main())


def test_stage_detects_in_place_mutation(run):
    """The staging memo is guarded by CONTENT, not identity alone: a
    loader mutating the same payload buffer in place between stagings
    gets a fresh upload, never the first staging's bytes."""

    async def main():
        import samples.presence  # noqa: F401

        engine = TensorEngine(config=TensorEngineConfig(tick_interval=0.0))
        keys = np.arange(32, dtype=np.int64)
        engine.arena_for("PresenceGrain").resolve_rows(keys)
        inj = engine.make_injector("PresenceGrain", "heartbeat", keys)
        games = (keys % 4).astype(np.int32)
        scores = np.ones(32, np.float32)
        a = inj.stage({"game": games, "score": scores, "tick": np.int32(1)})
        scores[:] = 7.0  # in-place reuse of the SAME buffer
        b = inj.stage({"game": games, "score": scores, "tick": np.int32(2)})
        np.testing.assert_array_equal(np.asarray(b["score"]), scores)
        assert a["game"] is b["game"]  # untouched leaves still memoize
        inj._staged = None  # nothing enqueued: just the guard contract

    run(main())


def test_rig_reports_per_run_pipeline_deltas(run):
    """run_presence_pipelined publishes THIS run's overlap/fallbacks —
    the bench reuses one engine across budgets and retry attempts, so
    the deltas of consecutive runs must partition the engine-lifetime
    counter instead of each re-reporting the cumulative total."""

    async def main():
        from samples.presence import run_presence_pipelined

        engine = TensorEngine(config=TensorEngineConfig(tick_interval=0.0))
        r1 = await run_presence_pipelined(engine, n_players=64, n_games=4,
                                          budget=0.05, n_ticks=4,
                                          warm_ticks=2)
        r2 = await run_presence_pipelined(engine, n_players=64, n_games=4,
                                          budget=0.05, n_ticks=4,
                                          warm_ticks=2)
        lifetime = engine.pipeline.overlap_seconds
        assert r1["overlap_s"] + r2["overlap_s"] == \
            pytest.approx(lifetime, abs=1e-5)
        assert r1["donation_fallbacks"] == 0
        assert r2["donation_fallbacks"] == 0

    run(main())


def test_note_tick_on_complete_stamps_in_executor(run):
    """note_tick(on_complete=...) runs the callback in the pipeline's
    own executor thread with the completion timestamp — one blocked
    thread serves both the rig's observation and the pipeline, instead
    of two threads blocking on the same fence."""
    import time as _time

    async def main():
        import samples.presence  # noqa: F401

        engine = TensorEngine(config=TensorEngineConfig(tick_interval=0.0))
        keys = np.arange(32, dtype=np.int64)
        engine.arena_for("PresenceGrain").resolve_rows(keys)
        engine.arena_for("GameGrain").resolve_rows(
            np.arange(4, dtype=np.int64))
        inj = engine.make_injector("PresenceGrain", "heartbeat", keys)
        games = (keys % 4).astype(np.int32)
        inj.inject({"game": games, "score": np.ones(32, np.float32),
                    "tick": np.int32(1)})
        engine.run_tick()
        stamps = []
        fut = engine.pipeline.note_tick(engine._tick_fence,
                                        on_complete=stamps.append)
        assert fut is not None
        await fut
        assert len(stamps) == 1
        assert 0.0 < stamps[0] <= _time.perf_counter()
        await engine.flush()

    run(main())


def test_pin_copy_compile_is_cause_attributed(run):
    """The copy-before-donate pin's jit compile is visible to the churn
    cause list like every other compile site: the first donated chain
    records a cause-coded event (cache-size delta — cache hits record
    nothing)."""

    async def main():
        from orleans_tpu.tensor.autofuse import _pin_copy

        # the pin jit cache is process-global: earlier donated tests may
        # already have compiled this column structure (in which case NO
        # event records — the no-phantom-events contract); clear it so
        # this engine's first donated chain really compiles
        getattr(_pin_copy, "_clear_cache", lambda: None)()
        engine = TensorEngine(config=_cfg(donate_state=True))
        keys = np.arange(64, dtype=np.int64)
        engine.arena_for("PipeLwwGrain").resolve_rows(keys)
        inj = engine.make_injector("PipeLwwGrain", "put", keys)
        for t in range(12):  # enough identical ticks to engage autofuse
            inj.inject({"v": np.full(64, t, np.int32)})
            engine.run_tick()
        await engine.flush()
        assert engine.autofuser.snapshot()["windows_run"] > 0
        pins = [e for e in engine.compile_tracker.events
                if str(e.get("key", "")).startswith("pin_copy:")]
        assert pins, "donated chain pin compile went unattributed"
        assert all(e["cause"] == "new_window" for e in pins)

    run(main())
