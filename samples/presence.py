"""Presence sample — heartbeat fan-in at 1M-grain scale (the north-star
benchmark workload).

Parity: reference Samples/Presence — PresenceGrain receives per-player
heartbeats and forwards game status to GameGrain
(reference: Samples/Presence/PresenceGrains/PresenceGrain.cs:40 →
GameGrain.UpdateGameStatus, GameGrain.cs:62; LoadGenerator project drives
it).

TPU-native shape: players and games are vector grains; a tick's heartbeats
arrive as one (player_key, payload) tensor, player rows update with
scatters, and the per-game fan-in (many players → one game) is a
``segment_sum`` — the batched equivalent of GameGrain's mailbox draining
thousands of UpdateGameStatus messages.
"""

from __future__ import annotations

import asyncio
import time
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from orleans_tpu.core.grain import batched_method
from orleans_tpu.tensor import (
    Batch,
    Emit,
    VectorGrain,
    field,
    scatter_rows,
    seg_sum,
    vector_grain,
)
from orleans_tpu.tensor.vector_grain import scatter_add_rows


@vector_grain
class PresenceGrain(VectorGrain):
    """Per-player presence state (reference: PresenceGrain.cs:40)."""

    last_heartbeat = field(jnp.int32, 0)   # tick of last heartbeat
    game = field(jnp.int32, -1)            # current game key
    heartbeats = field(jnp.int32, 0)       # lifetime heartbeat count

    @batched_method
    @staticmethod
    def heartbeat(state, batch: Batch, n_rows: int):
        """Record the heartbeat and forward game status to the game grain
        (reference: PresenceGrain.Heartbeat → GameGrain.UpdateGameStatus)."""
        rows, args = batch.rows, batch.args
        ones = jnp.ones_like(args["game"], dtype=jnp.int32)
        tick = jnp.broadcast_to(jnp.asarray(args["tick"], jnp.int32),
                                rows.shape)
        state = {
            **state,
            "last_heartbeat": scatter_rows(state["last_heartbeat"], rows,
                                           tick),
            "game": scatter_rows(state["game"], rows, args["game"]),
            "heartbeats": scatter_add_rows(state["heartbeats"], rows, ones),
        }
        emit = Emit(
            interface="GameGrain", method="update_game_status",
            keys=args["game"],
            args={"score": args["score"], "count": ones},
            mask=batch.mask)
        return state, None, (emit,)


@vector_grain
class GameGrain(VectorGrain):
    """Per-game aggregate (reference: GameGrain.cs:62)."""

    total_score = field(jnp.float32, 0.0)
    updates = field(jnp.int32, 0)

    @batched_method
    @staticmethod
    def update_game_status(state, batch: Batch, n_rows: int):
        rows, args = batch.rows, batch.args
        state = {
            **state,
            "total_score": state["total_score"]
            + seg_sum(args["score"], rows, n_rows),
            "updates": state["updates"] + seg_sum(args["count"], rows, n_rows),
        }
        return state


# ---------------------------------------------------------------------------
# load generator (reference: Samples/Presence/LoadGenerator)
# ---------------------------------------------------------------------------

async def run_presence_load(engine, n_players: int = 100_000,
                            n_games: Optional[int] = None,
                            n_ticks: int = 10,
                            seed: int = 0,
                            device_payloads: bool = True,
                            measure_latency: bool = False,
                            warm_ticks: int = 0) -> Dict[str, float]:
    """Drive ``n_ticks`` of heartbeats from every player; returns stats.

    Each tick is 2 logical messages per player (player heartbeat + game
    update), matching how the reference counts Presence traffic.

    ``device_payloads=True`` models a gateway whose heartbeat buffers live
    in device memory (the load generator is colocated, like the reference's
    in-process LoadGenerator); False pays the full host→device injection
    cost every tick.

    ``measure_latency=True`` blocks on device completion *every tick* and
    records each tick's inject→completion wall time, so the returned
    ``tick_p99_seconds`` is a true 99th percentile of turn latency (a
    message injected at a tick boundary completes within that tick).  This
    serializes ticks, so throughput should be read from a pipelined run
    (``measure_latency=False``) and latency from a synced run.
    """
    n_games = n_games or max(1, n_players // 100)
    rng = np.random.default_rng(seed)
    players = np.arange(n_players, dtype=np.int64)
    games = rng.integers(0, n_games, n_players).astype(np.int32)
    scores = rng.random(n_players, dtype=np.float32)

    # pre-size arenas so the measured loop has no growth pauses
    engine.arena_for("PresenceGrain").reserve(n_players)
    engine.arena_for("GameGrain").reserve(n_games)

    # resolve the destination set once (steady-state client edge)
    injector = engine.make_injector("PresenceGrain", "heartbeat", players)

    if device_payloads:
        games_d = jnp.asarray(games)
        scores_d = jnp.asarray(scores)

        def args_for(t: int):
            # tick rides as a scalar leaf — broadcast inside the kernel
            return {"game": games_d, "score": scores_d,
                    "tick": np.int32(t + 1)}
    else:
        def args_for(t: int):
            return {"game": games, "score": scores,
                    "tick": np.full(n_players, t + 1, dtype=np.int32)}

    import jax as _jax
    game_arena = engine.arena_for("GameGrain")
    tick_durations = []

    # untimed warm phase through the SAME injector: amortizes compiles
    # AND lets transparent auto-fusion engage before the timed window
    # (the signature keys on the injector's cached arrays, so a separate
    # warm call with a fresh injector would not warm the fused program)
    for t in range(warm_ticks):
        injector.inject(args_for(t))
        await engine.drain_queues()
    if warm_ticks:
        await engine.flush()
        _jax.block_until_ready(game_arena.state["updates"])

    t0 = time.perf_counter()
    for t in range(n_ticks):
        tick_t0 = time.perf_counter()
        injector.inject(args_for(t))
        if measure_latency:
            # synced mode: a tick's messages are fully applied (including
            # the game-grain fan-in emitted inside the tick) before the
            # next tick starts — the recorded duration IS the turn latency
            # of that tick's messages
            await engine.flush()
            # re-read state each tick: step kernels donate their input
            # buffers, so arena.state is a fresh array every tick
            _jax.block_until_ready(game_arena.state["updates"])
            tick_durations.append(time.perf_counter() - tick_t0)
        else:
            # pipelined dispatch: the next tick's heartbeats stream in
            # while this tick computes (miss-checks settle at final flush)
            await engine.drain_queues()
    await engine.flush()
    # wait for the device stream so we time real completion, not dispatch
    _jax.block_until_ready(engine.arena_for("GameGrain").state["updates"])
    elapsed = time.perf_counter() - t0

    messages = 2 * n_players * n_ticks  # heartbeat + game update per player
    stats: Dict[str, float] = {
        "players": n_players,
        "games": n_games,
        "ticks": n_ticks,
        "seconds": elapsed,
        "messages": messages,
        "messages_per_sec": messages / elapsed,
        "mean_tick_seconds": elapsed / n_ticks,
        # transparent auto-fusion may have engaged mid-run (the loader
        # only ever calls inject()); report how much of the run it took
        "autofuse": engine.autofuser.snapshot(),
    }
    if tick_durations:
        d = np.asarray(tick_durations)
        stats["tick_p50_seconds"] = float(np.percentile(d, 50))
        stats["tick_p99_seconds"] = float(np.percentile(d, 99))
        stats["tick_max_seconds"] = float(d.max())
    return stats


async def run_presence_load_fused(engine, n_players: int = 100_000,
                                  n_games: Optional[int] = None,
                                  n_ticks: int = 20, window: int = 20,
                                  seed: int = 0,
                                  measure_latency: bool = False
                                  ) -> Dict[str, float]:
    """The same Presence load through the FUSED tick path
    (tensor/fused.py): windows of up to ``window`` ticks execute as one
    compiled program — heartbeat kernel, dense directory resolve of the
    game emits, and game fan-in all inside one ``lax.scan``.  The steady
    payload (game assignment, score) rides as static args; only the tick
    counter is scanned.  ``measure_latency=True`` uses windows of ONE
    tick and blocks per window, so the recorded durations are true
    per-tick turn latencies.  Delivery exactness is asserted via the
    program's device-side miss counter."""
    import jax as _jax

    n_games = n_games or max(1, n_players // 100)
    rng = np.random.default_rng(seed)
    players = np.arange(n_players, dtype=np.int64)

    engine.arena_for("PresenceGrain").reserve(n_players)
    engine.arena_for("GameGrain").reserve(n_games)
    # steady state: every destination is activated before the window
    engine.arena_for("GameGrain").resolve_rows(
        np.arange(n_games, dtype=np.int64))
    prog = engine.fuse_ticks("PresenceGrain", "heartbeat", players)

    static = {"game": jnp.asarray(
        rng.integers(0, n_games, n_players).astype(np.int32)),
        "score": jnp.asarray(rng.random(n_players, dtype=np.float32))}
    game_arena = engine.arena_for("GameGrain")
    tick_durations = []

    from orleans_tpu.tensor.fused import plan_windows
    if measure_latency:
        window = 1
    window, n_windows, n_ticks = plan_windows(window, n_ticks)

    # untimed warm window: compilation is a one-time cost, not steady
    # state (the unfused loader warms the same way via its caller)
    prog.run({"tick": jnp.arange(1, window + 1, dtype=jnp.int32)},
             static_args=static)
    _jax.block_until_ready(game_arena.state["updates"])

    t0 = time.perf_counter()
    for w in range(n_windows):
        base = (w + 1) * window  # continue past the warm window's ticks
        stacked = {"tick": jnp.arange(base + 1, base + window + 1,
                                      dtype=jnp.int32)}
        w0 = time.perf_counter()
        prog.run(stacked, static_args=static)
        if measure_latency:
            _jax.block_until_ready(game_arena.state["updates"])
            tick_durations.append(time.perf_counter() - w0)
    _jax.block_until_ready(game_arena.state["updates"])
    elapsed = time.perf_counter() - t0
    misses = prog.verify()
    if misses:  # not assert: -O must not skip exactness verification
        raise RuntimeError(
            f"fused window touched {misses} unactivated grains")

    messages = 2 * n_players * n_ticks
    stats: Dict[str, float] = {
        "players": n_players, "games": n_games, "ticks": n_ticks,
        "seconds": elapsed, "messages": messages,
        "messages_per_sec": messages / elapsed,
        "mean_tick_seconds": elapsed / n_ticks,
        "engine": "fused",
        # the untimed warm window ran too; misses is the device counter
        # read above (0, or this function raised)
        "warm_ticks": window,
        "misses": misses,
    }
    if tick_durations:
        d = np.asarray(tick_durations)
        stats["tick_p50_seconds"] = float(np.percentile(d, 50))
        stats["tick_p99_seconds"] = float(np.percentile(d, 99))
        stats["tick_max_seconds"] = float(d.max())
    return stats


async def measure_event_floor(repeats: int = 9) -> "Tuple[float, float]":
    """The rig's EVENT-DRIVEN observation floor: the wall time for a
    completion FUTURE to resolve for a trivial already-dispatched device
    program — the successor of the old ``measure_sync_floor`` blocking
    probe.  The engine no longer blocks on the dispatch path at all
    (completion is observed by an executor thread resolving an asyncio
    future the moment the device signals — engine.TickPipeline), so
    this is the only observation cost the latency rig pays, and it sits
    OFF the dispatch path: it delays the *timestamp*, never the next
    tick.  Returns ``(median, p95)`` — published as ``sync_floor_s``
    for artifact continuity; the acceptance bar is ≤ 5ms."""
    import asyncio as _asyncio
    import jax as _jax

    loop = _asyncio.get_running_loop()
    x = jnp.ones((256,), jnp.float32)
    probe = _jax.jit(lambda a: a * 2.0)
    probe(x).block_until_ready()  # compile
    # warm the executor pool: the FIRST run_in_executor spawns a thread,
    # which is pool setup cost, not observation cost
    await loop.run_in_executor(None, _jax.block_until_ready, probe(x))
    samples = []
    for _ in range(repeats):
        y = probe(x)
        t0 = time.perf_counter()
        await loop.run_in_executor(None, _jax.block_until_ready, y)
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples)), float(np.percentile(samples, 95))


async def run_presence_ledger_point(engine, n_players: int, n_games: int,
                                    budget: float,
                                    offered_rate: Optional[float] = None,
                                    n_ticks: int = 48, warm_ticks: int = 8,
                                    seed: int = 0) -> Dict[str, float]:
    """One latency operating point measured by the ON-DEVICE ledger
    (tensor/ledger.py) — the device-side companion to
    run_presence_pipelined:
    the host never observes per-tick completion at all.

    Closed loop per tick: sleep the accumulation interval, inject the
    heartbeats a rate-``offered_rate`` producer generated in that
    window (rounded down to a precompiled injector ladder rung), run
    the tick — WITHOUT blocking on completion.  Each message's
    inject→completion tick delta accumulates into the device ledger's
    per-(type, method) log2 histogram inside the tick; the host syncs
    ONCE at the end, so the rig's ~100ms completion-observation floor
    is paid once per RUN and amortizes into seconds-per-tick instead of
    flooring every sample.  No sync-floor subtraction happens anywhere:
    the floor never entered the measurement.

    Returns per-method p50/p99 in device ticks plus the tick→seconds
    conversion (wall elapsed / ticks) and the derived p50/p99 seconds.
    Drive it on an engine with auto-fusion OFF so the deltas carry the
    unfused queue-wait semantics (a fused window's deltas are 0 by the
    virtual tick clock — see tensor/fused.py)."""
    import jax as _jax

    rng = np.random.default_rng(seed)
    players = np.arange(n_players, dtype=np.int64)
    games = rng.integers(0, n_games, n_players).astype(np.int32)
    scores = rng.random(n_players, dtype=np.float32)

    engine.arena_for("PresenceGrain").reserve(n_players)
    engine.arena_for("GameGrain").reserve(n_games)
    engine.arena_for("PresenceGrain").resolve_rows(players)
    engine.arena_for("GameGrain").resolve_rows(
        np.arange(n_games, dtype=np.int64))

    ladder = [m for m in (2048, 8192, 32768, 131072, 524288)
              if m < n_players] + [n_players]
    rungs = [{"m": m,
              "inj": engine.make_injector("PresenceGrain", "heartbeat",
                                          players[:m]),
              "game": jnp.asarray(games[:m]),
              "score": jnp.asarray(scores[:m])}
             for m in ladder]
    interval = budget * 0.5
    if offered_rate is None:
        offered_rate = rungs[-1]["m"] / budget

    game_arena = engine.arena_for("GameGrain")

    def inject_for(accumulated: float) -> int:
        m_target = offered_rate * accumulated
        rung = rungs[0]
        for r in rungs:
            if r["m"] <= m_target:
                rung = r
        rung["inj"].inject({"game": rung["game"], "score": rung["score"],
                            "tick": np.int32(engine.tick_number + 1)})
        return rung["m"]

    # warm: compiles + first activations settle outside the measurement
    for _ in range(warm_ticks):
        inject_for(interval)
        engine.run_tick()
    await engine.flush()
    _jax.block_until_ready(game_arena.state["updates"])
    engine.ledger.reset()

    messages = 0
    window_start = time.perf_counter()
    t0 = window_start
    for _ in range(n_ticks):
        await asyncio.sleep(interval)
        now = time.perf_counter()
        messages += 2 * inject_for(now - window_start)
        window_start = now
        engine.run_tick()
    await engine.flush()
    # the ONE completion observation of the whole run
    _jax.block_until_ready(game_arena.state["updates"])
    elapsed = time.perf_counter() - t0

    seconds_per_tick = elapsed / n_ticks
    by_method = {}
    for method, h in engine.ledger.snapshot().items():
        by_method[method] = {
            "p50_ticks": h["p50_ticks"],
            "p99_ticks": h["p99_ticks"],
            "p50_s": round(h["p50_ticks"] * seconds_per_tick, 6),
            "p99_s": round(h["p99_ticks"] * seconds_per_tick, 6),
            "messages": h["total"],
        }
    head = by_method.get("PresenceGrain.heartbeat",
                         next(iter(by_method.values()), {}))
    return {
        "budget_s": budget,
        "offered_rate": offered_rate,
        "messages": messages,
        "seconds": elapsed,
        "messages_per_sec": messages / elapsed,
        "ticks": n_ticks,
        "seconds_per_tick": seconds_per_tick,
        "p50_ticks": head.get("p50_ticks", 0.0),
        "p99_ticks": head.get("p99_ticks", 0.0),
        "p50_s": head.get("p50_s", 0.0),
        "p99_s": head.get("p99_s", 0.0),
        "honored": bool(head.get("p99_s", 0.0) <= budget),
        "by_method": by_method,
        "measurement": "on-device ledger (tick deltas); one completion "
                       "observation per run; no sync-floor subtraction",
    }


async def run_presence_pipelined(engine, n_players: int, n_games: int,
                                 budget: float,
                                 offered_rate: Optional[float] = None,
                                 n_ticks: int = 40, warm_ticks: int = 10,
                                 pipeline_depth: int = 2,
                                 seed: int = 0) -> Dict[str, float]:
    """One latency-bounded operating point, measured with EVENT-DRIVEN
    completion and pipelined dispatch — the honest 10ms mode that
    replaced ``run_presence_bounded``'s blocking rig.

    Closed loop per tick: sleep the accumulation interval, dispatch the
    heartbeats a rate-``offered_rate`` producer generated in that
    window (rounded down to a precompiled batch-size ladder rung) as
    ONE fused single-tick program with DONATED state buffers, then move
    straight on — the dispatch path never blocks.  Each tick's
    completion is observed by an executor thread that timestamps the
    device's completion signal for the tick's FENCE (an output nothing
    donates), so the recorded duration window-start→completion-event is
    the turn latency of the tick's OLDEST message with NO polling floor
    and NO sync-floor subtraction: the floor is gone, not netted out.
    Up to ``pipeline_depth`` ticks ride in flight (the engine pipeline's
    event-driven backpressure), so tick N+1's dispatch overlaps tick
    N's device execution — donation makes that safe (XLA
    double-buffers the columns in place).

    ``offered_rate=None`` estimates the highest sustainable rate from
    measured per-rung service times; the caller verifies p99 ≤ budget
    and retries lower if the estimate overshot.
    Delivery exactness is asserted via the programs' device-side miss
    counters at the end of the run."""
    import asyncio as _asyncio

    cfg = engine.config
    cfg.target_tick_latency = budget
    cfg.pipeline_depth = max(1, int(pipeline_depth))
    cfg.low_latency = True
    pipeline = engine.pipeline

    # the rung ladder (programs + compiles + measured service times) is
    # cached on the engine: a caller retrying this function at a lower
    # rate on one engine would otherwise rebuild ~6 fused programs per
    # attempt, almost all compile wall time on a slow rig
    cache = getattr(engine, "_pipelined_rung_cache", None)
    if cache is not None and cache["key"] == (n_players, n_games, seed):
        rungs, service = cache["rungs"], cache["service"]
    else:
        rng = np.random.default_rng(seed)
        players = np.arange(n_players, dtype=np.int64)
        games = rng.integers(0, n_games, n_players).astype(np.int32)
        scores = rng.random(n_players, dtype=np.float32)

        engine.arena_for("PresenceGrain").reserve(n_players)
        engine.arena_for("GameGrain").reserve(n_games)
        # activate everything up front: the bounded loop measures steady
        # state, not cold activation
        engine.arena_for("PresenceGrain").resolve_rows(players)
        engine.arena_for("GameGrain").resolve_rows(
            np.arange(n_games, dtype=np.int64))

        # batch-size ladder: one compiled window=1 program per prefix
        # size, so variable offered load maps to a bounded set of
        # compiled shapes (finer rungs at the bottom — the 10ms budget
        # lands there on slow rigs, and the rate search needs steps)
        ladder = [m for m in (2048, 4096, 8192, 16384, 32768, 65536,
                              131072, 262144, 524288)
                  if m < n_players] + [n_players]
        rungs = []
        for m in ladder:
            rungs.append({
                "m": m,
                "prog": engine.fuse_ticks("PresenceGrain", "heartbeat",
                                          players[:m]),
                "static": {"game": jnp.asarray(games[:m]),
                           "score": jnp.asarray(scores[:m])},
            })

        # warm pass: compile each rung (rep 1), then measure its service
        # time event-driven (median of 3 — one noisy sample must not
        # steer the operating point on a shared rig)
        service = {}
        for rung in rungs:
            rung["prog"].run({"tick": np.full(1, 1, np.int32)},
                             static_args=rung["static"])
            await engine.wait_completion()
            reps = []
            for rep in range(3):
                s0 = time.perf_counter()
                rung["prog"].run({"tick": np.full(1, 1, np.int32)},
                                 static_args=rung["static"])
                await engine.wait_completion()
                reps.append(time.perf_counter() - s0)
            service[rung["m"]] = float(np.median(reps))
        engine._pipelined_rung_cache = {"key": (n_players, n_games, seed),
                                        "rungs": rungs, "service": service}

    # accumulation interval: 40% of the budget goes to queue-wait; the
    # rest is service + completion-event headroom
    interval = budget * 0.4
    if offered_rate is None:
        # largest rung whose measured service leaves p99 headroom:
        # oldest-message latency ≈ interval + service, so require
        # service ≤ 50% of budget (10% margin for event jitter)
        candidates = [m / interval for m, s in service.items()
                      if s <= 0.5 * budget]
        offered_rate = max(candidates) if candidates \
            else rungs[0]["m"] / budget

    records = []
    futs = []
    tick_counter = 0
    # per-run pipeline accounting: the bench reuses ONE engine across
    # budgets and retry attempts, so the published point must carry
    # THIS run's overlap/fallbacks/high-water — not the engine lifetime
    overlap0 = pipeline.overlap_seconds
    fallbacks0 = engine.donation_fallbacks
    pipeline.max_inflight = 0
    window_start = time.perf_counter()
    for t in range(warm_ticks + n_ticks):
        await _asyncio.sleep(interval)
        accumulated = time.perf_counter() - window_start
        m_target = offered_rate * accumulated
        rung = rungs[0]
        for r in rungs:
            if r["m"] <= m_target:
                rung = r
        tick_counter += 1
        # ONE dispatch; no blocking — the completion event does the
        # timestamping off the dispatch path
        rung["prog"].run({"tick": np.full(1, tick_counter, np.int32)},
                         static_args=rung["static"])
        rec = {"start": window_start, "done": None, "m": rung["m"],
               "measured": t >= warm_ticks}
        records.append(rec)
        # engine-pipeline bookkeeping + depth backpressure: with
        # pipeline_depth ticks in flight, await the OLDEST completion
        # event before dispatching another.  The on_complete callback
        # timestamps IN the pipeline's executor thread the moment the
        # device signals — the event IS the observation, and the one
        # blocked thread serves both the rig and the pipeline
        fut = pipeline.note_tick(
            engine._tick_fence,
            on_complete=lambda ts, rec=rec: rec.__setitem__("done", ts))
        if fut is not None:
            futs.append(fut)
        await pipeline.throttle()
        window_start = time.perf_counter()
    await _asyncio.gather(*futs)
    await engine.wait_completion()
    # exactness: every window resolved every emit in the frozen mirror
    for rung in rungs:
        misses = rung["prog"].verify()
        if misses:  # not assert: -O must not skip exactness verification
            raise RuntimeError(
                f"pipelined fused tick touched {misses} unactivated "
                "grains")

    measured = [r for r in records if r["measured"] and r["done"]]
    d = np.asarray([r["done"] - r["start"] for r in measured])
    messages = int(sum(2 * r["m"] for r in measured))
    # wall span of the measured segment: first window start → last
    # completion EVENT (completions may land out of band — pipelined)
    elapsed = max(r["done"] for r in measured) \
        - min(r["start"] for r in measured)
    p99 = float(np.percentile(d, 99))
    return {
        "budget_s": budget,
        "offered_rate": offered_rate,
        "messages": messages,
        "seconds": elapsed,
        "messages_per_sec": messages / elapsed,
        "tick_p50_seconds": float(np.percentile(d, 50)),
        "tick_p99_seconds": p99,
        "tick_max_seconds": float(d.max()),
        "mean_batch": float(np.mean([r["m"] for r in measured])),
        "ticks": len(measured),
        "pipeline_depth": cfg.pipeline_depth,
        "inflight_max": pipeline.max_inflight,
        "overlap_s": round(pipeline.overlap_seconds - overlap0, 6),
        "donation_fallbacks": engine.donation_fallbacks - fallbacks0,
        # no floor, no netting: completion is the device's event, and
        # honored is a direct observation — strict IS the headline
        "honored": bool(p99 <= budget),
        "honored_strict": bool(p99 <= budget),
        "measurement": "event-driven completion (executor-thread "
                       "timestamp on the tick fence); pipelined "
                       "dispatch with donated state; no sync-floor "
                       "subtraction — the dispatch path never blocks",
    }
