"""Tensor data-plane tests: arenas, batched dispatch, emits, proxy interop.

Reference analog: there is no reference analog — this is the rebuild's
batched replacement for Dispatcher/Scheduler hot-path behavior, tested for
the same *semantic* guarantees (per-grain fan-in equals sequential mailbox
drain for commutative updates; auto-activation on first message).
"""

import asyncio

import jax.numpy as jnp
import numpy as np

from orleans_tpu.core.grain import batched_method
from orleans_tpu.tensor import (
    Batch,
    TensorEngine,
    VectorGrain,
    field,
    seg_sum,
    vector_grain,
)
from orleans_tpu.tensor.arena import GrainArena
from orleans_tpu.tensor.vector_grain import scatter_add_rows, vector_type

from samples.presence import GameGrain, PresenceGrain, run_presence_load


@vector_grain
class AccumGrain(VectorGrain):
    total = field(jnp.float32, 0.0)
    count = field(jnp.int32, 0)

    @batched_method
    @staticmethod
    def add(state, batch: Batch, n_rows: int):
        state = {
            **state,
            "total": state["total"] + seg_sum(batch.args["v"], batch.rows,
                                              n_rows),
            "count": state["count"] + seg_sum(
                jnp.ones_like(batch.rows, dtype=jnp.int32) * batch.mask,
                batch.rows, n_rows),
        }
        results = {"echo": batch.args["v"] * 2}
        return state, results, ()


def test_arena_resolve_and_autoactivate():
    engine = TensorEngine()
    arena = engine.arena_for("AccumGrain")
    keys = np.array([5, 7, 5, 9], dtype=np.int64)
    rows = arena.resolve_rows(keys)
    assert rows[0] == rows[2] and rows[0] != rows[1]
    assert arena.live_count == 3
    # stable across calls
    rows2 = arena.resolve_rows(keys)
    np.testing.assert_array_equal(rows, rows2)


def test_arena_growth_preserves_state(run):
    async def main():
        engine = TensorEngine(initial_capacity=8)
        engine.send_batch("AccumGrain", "add", np.array([1]),
                          {"v": np.array([10.0], np.float32)})
        await engine.flush()
        arena = engine.arena_for("AccumGrain")
        # force several growths
        arena.resolve_rows(np.arange(100, 200, dtype=np.int64))
        row = arena.read_row(1)
        assert row is not None and float(row["total"]) == 10.0

    run(main())


def test_batched_fan_in_matches_sequential(run):
    async def main():
        engine = TensorEngine()
        keys = np.array([1, 2, 1, 1, 2], dtype=np.int64)
        vals = np.array([1.0, 2.0, 3.0, 4.0, 5.0], dtype=np.float32)
        fut = engine.send_batch("AccumGrain", "add", keys, {"v": vals},
                                want_results=True)
        await engine.flush()
        arena = engine.arena_for("AccumGrain")
        assert float(arena.read_row(1)["total"]) == 8.0   # 1+3+4
        assert float(arena.read_row(2)["total"]) == 7.0   # 2+5
        assert int(arena.read_row(1)["count"]) == 3
        res = fut.result()
        np.testing.assert_allclose(res["echo"], vals * 2)

    run(main())


def test_bucket_padding_does_not_corrupt(run):
    async def main():
        engine = TensorEngine()
        # 3 messages → padded to bucket 256; pads must not touch row 0
        keys = np.array([3, 4, 5], dtype=np.int64)
        engine.send_batch("AccumGrain", "add", keys,
                          {"v": np.ones(3, np.float32)})
        await engine.flush()
        arena = engine.arena_for("AccumGrain")
        for k in (3, 4, 5):
            assert float(arena.read_row(k)["total"]) == 1.0
            assert int(arena.read_row(k)["count"]) == 1

    run(main())


def test_presence_emit_chain(run):
    async def main():
        engine = TensorEngine()
        n_players, n_games = 1000, 10
        stats = await run_presence_load(engine, n_players=n_players,
                                        n_games=n_games, n_ticks=3)
        assert stats["messages"] == 2 * n_players * 3
        game_arena = engine.arena_for("GameGrain")
        assert game_arena.live_count == n_games
        total_updates = sum(
            int(game_arena.read_row(g)["updates"]) for g in range(n_games))
        assert total_updates == n_players * 3
        presence = engine.arena_for("PresenceGrain")
        assert presence.live_count == n_players
        assert int(presence.read_row(0)["heartbeats"]) == 3

    run(main())


def test_cold_device_keys_emit_once(run):
    """Heartbeats to unseen players by DEVICE keys miss optimistic
    resolution and redeliver after activation; the game update each
    emits must land once, not once per delivery attempt."""

    async def main():
        engine = TensorEngine()
        n, n_games = 512, 8
        keys = np.arange(n, dtype=np.int32)
        games = keys % n_games
        engine.send_batch("PresenceGrain", "heartbeat", jnp.asarray(keys),
                          {"game": jnp.asarray(games),
                           "score": jnp.ones(n, jnp.float32),
                           "tick": jnp.ones(n, jnp.int32)})
        await engine.flush()
        assert engine.activation_passes >= 1
        game = engine.arena_for("GameGrain")
        rows, found = game.lookup_rows(np.arange(n_games, dtype=np.int64))
        assert found.all()
        np.testing.assert_array_equal(
            np.asarray(game.state["updates"])[rows],
            np.bincount(games, minlength=n_games))

    run(main())


def test_proxy_call_routes_to_engine(run):
    """Vector grains remain callable through normal grain references."""

    async def main():
        from orleans_tpu.runtime.silo import Silo

        silo = Silo(name="tensor-proxy")
        await silo.start()
        try:
            factory = silo.attach_client()
            ref = factory.get_grain("AccumGrain", 77)
            res = await ref.add({"v": np.float32(21.0)})
            assert float(res["echo"]) == 42.0
            arena = silo.tensor_engine.arena_for("AccumGrain")
            assert float(arena.read_row(77)["total"]) == 21.0
        finally:
            await silo.stop()

    run(main())


def test_multi_round_tick_caps_and_spills(run):
    """Emit chains longer than max_rounds_per_tick spill to the next tick
    (the analog of MaxForwardCount bounding intra-tick chains)."""

    async def main():
        engine = TensorEngine()
        engine.config.max_rounds_per_tick = 2
        n = 100
        stats = await run_presence_load(engine, n_players=n, n_games=2,
                                        n_ticks=1)
        # heartbeat round + game round both fit in one tick here
        assert engine.rounds_run >= 2
        assert stats["messages"] == 2 * n

    run(main())


def test_latency_stats_are_true_percentiles(run):
    """snapshot()['tick_latency'] reports real percentiles over per-tick
    durations, not a mean (VERDICT r1: the published p99 was a mean)."""

    async def main():
        engine = TensorEngine()
        stats = await run_presence_load(engine, n_players=500, n_games=5,
                                        n_ticks=8, measure_latency=True)
        assert "tick_p99_seconds" in stats
        assert stats["tick_p99_seconds"] >= stats["tick_p50_seconds"] > 0
        lat = engine.latency_stats()
        assert lat["n"] >= 8
        assert lat["max"] >= lat["p99"] >= lat["p50"] > 0
        assert lat["p99"] <= lat["max"]

    run(main())


def test_adaptive_tick_interval_controller():
    """With a latency budget set, overruns shrink the accumulation interval
    multiplicatively and headroom grows it back, clamped to the bounds
    (SURVEY §7 hard-part 5: adaptive tick sizing)."""
    engine = TensorEngine()
    cfg = engine.config
    cfg.target_tick_latency = 0.010
    cfg.tick_interval_min = 0.0002
    cfg.tick_interval_max = 0.05
    engine._adaptive_interval = 0.004

    # tick far over budget: interval halves
    engine._adapt(tick_duration=0.050)
    assert engine._adaptive_interval == 0.002
    # repeated overruns clamp at the floor
    for _ in range(20):
        engine._adapt(tick_duration=0.050)
    assert engine._adaptive_interval == cfg.tick_interval_min
    assert engine.tick_interval() == cfg.tick_interval_min

    # fast ticks: interval recovers but never exceeds half the headroom
    for _ in range(200):
        engine._adapt(tick_duration=0.001)
    assert engine._adaptive_interval <= (cfg.target_tick_latency - 0.001) / 2
    assert engine._adaptive_interval > cfg.tick_interval_min

    # no budget -> fixed interval
    cfg.target_tick_latency = 0.0
    assert engine.tick_interval() == cfg.tick_interval


def test_turn_observer_tolerates_cancellation(run):
    """Non-graceful stop cancels in-flight turns; the done-callback must
    not re-raise CancelledError (VERDICT r1: bench teardown spewed
    unhandled CancelledError tracebacks)."""

    async def main():
        from orleans_tpu.runtime.activation import _observe_turn

        async def hang():
            await asyncio.sleep(30)

        task = asyncio.get_running_loop().create_task(hang())
        await asyncio.sleep(0)
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass
        _observe_turn(task)  # must not raise

        async def boom():
            raise RuntimeError("x")

        task2 = asyncio.get_running_loop().create_task(boom())
        try:
            await task2
        except RuntimeError:
            pass
        _observe_turn(task2)  # marks retrieved, must not raise

    run(main())


def test_wide_keys_resolve_on_device(run):
    """Keys beyond int32 route on DEVICE through the two-level
    hash/bucket mirror (arena.device_index_wide; the r3-era refusal is
    gone — only the NARROW mirror still refuses wide keys, because the
    wide one serves them).  Host-path dispatch and results keep working
    unchanged."""

    async def main():
        import pytest
        import jax.numpy as _jnp
        from orleans_tpu.tensor.arena import split_wide_keys
        from orleans_tpu.tensor.engine import resolve_rows_on_device

        engine = TensorEngine()
        arena = engine.arena_for("AccumGrain")
        wide = np.array([2**40 + 1, 2**40 + 2], dtype=np.int64)

        # host path: resolution, dispatch and results all work
        fut = engine.send_batch("AccumGrain", "add", wide,
                                {"v": np.float32([1.0, 2.0])},
                                want_results=True)
        await engine.flush()
        res = await fut
        np.testing.assert_allclose(res["echo"], [2.0, 4.0])
        rows = arena.resolve_rows(wide)
        assert arena.live_count >= 2 and rows[0] != rows[1]

        # device path: the wide mirror resolves the same keys to the
        # same rows, entirely on device
        hi, lo = split_wide_keys(wide)
        drows, miss = resolve_rows_on_device(
            arena, (_jnp.asarray(hi), _jnp.asarray(lo)),
            _jnp.ones(2, dtype=bool))
        assert int(miss) == 0
        np.testing.assert_array_equal(np.asarray(drows), rows)

        # the narrow int32 mirror still refuses loudly (it cannot hold
        # these keys); the wide mirror is the supported path
        with pytest.raises(OverflowError, match="int32"):
            arena.device_index()

    run(main())


def test_per_stage_tick_profiling_names_the_slow_stage(run):
    """The tick pipeline is profiled per stage (resolve/apply/route/...),
    the StageAnalysis analog (reference: src/Orleans/Statistics/
    StageAnalysis.cs:81): a slow tick must be attributable to a stage."""

    async def main():
        import time as _time

        engine = TensorEngine()
        keys = np.arange(64, dtype=np.int64)
        engine.send_batch("AccumGrain", "add", keys,
                          {"v": np.float32(np.ones(64))})
        await engine.flush()
        snap = engine.snapshot()
        stages = snap["stages"]
        assert {"resolve", "apply", "route"} <= set(stages)
        assert all(v >= 0 for v in stages.values())
        # stage sum cannot exceed total tick wall time
        assert sum(snap["last_tick_stages"].values()) <= \
            max(engine.tick_durations) + 1e-6

        # make resolution artificially slow; the breakdown must name it
        arena = engine.arena_for("AccumGrain")
        orig = arena.resolve_rows

        def slow_resolve(*a, **kw):
            _time.sleep(0.05)
            return orig(*a, **kw)

        arena.resolve_rows = slow_resolve
        engine.stage_seconds.clear()
        engine.send_batch("AccumGrain", "add", keys,
                          {"v": np.float32(np.ones(64))})
        await engine.flush()
        stages = engine.snapshot()["stages"]
        assert max(stages, key=stages.get) == "resolve"
        assert stages["resolve"] >= 0.05

    run(main())


def test_scatter_helpers_drop_padding_rows():
    """scatter_rows / scatter_add_rows must DROP padding rows (-1), not
    let JAX's negative-index normalization wrap them onto the LAST row —
    once an arena fills, that wrap silently corrupts whichever grain
    lives there (the padded host-batch path hits this every tick)."""
    import jax.numpy as jnp

    from orleans_tpu.tensor.vector_grain import (
        scatter_add_rows,
        scatter_rows,
    )

    col = jnp.zeros(4, jnp.int32)
    rows = jnp.asarray([-1, 1, -1, 3])
    vals = jnp.asarray([9, 5, 9, 7], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(scatter_rows(col, rows, vals)), [0, 5, 0, 7])
    np.testing.assert_array_equal(
        np.asarray(scatter_add_rows(col, rows, vals)), [0, 5, 0, 7])
