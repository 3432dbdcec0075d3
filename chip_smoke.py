"""Chip smoke: the silo and its Presence tick engine, once, on the chip.

``python chip_smoke.py`` boots a ``Silo`` (``MemoryStorage``, attached
client), answers ``IHello.say_hello`` calls, then drives Presence at
1,000,000 players / 10,000 games on the silo's own engine: cold
activation of every player through the miss path, unfused ticks, one
fused window, the autofused path, and heartbeats through grain
references.  After each Presence phase the device state is compared
with a NumPy replay of the same seed.

``python chip_smoke.py --chips 4`` runs only the sharded-arena phase:
the same load on a 4-device mesh, compared with a one-device run in the
same process.

Every phase prints one line (wall time, the engine's compile count, the
device's peak bytes).  The last line of stdout is the JSON ``ok`` object,
printed only when every phase passed on a TPU.  On any other platform
the script exits non-zero before running anything.

Tolerances: integer columns must match exactly.  A float32 sum of k
terms accumulated in any order is within ``k * 2**-24 * sum(|x|)`` of
the exact sum (the recursive-summation bound), so each game's
``total_score`` must lie within that of the float64 replay, k being the
game's update count.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from typing import Callable, Optional

import numpy as np

#: unit roundoff of float32 — the per-term factor of the float bound
F32_U = 2.0 ** -24
#: Presence at the width of the benchmark's presence-1m configuration
N_PLAYERS = 1_000_000
N_GAMES = 10_000


class Replay:
    """NumPy replay of Presence traffic for one seed.  Player ``i``
    always heartbeats with game ``games[i]`` and score ``scores[i]`` —
    the draws ``samples.presence``'s loaders make from the same seed."""

    def __init__(self, n_players: int, n_games: int, seed: int) -> None:
        rng = np.random.default_rng(seed)
        self.players = np.arange(n_players, dtype=np.int64)
        self.games = rng.integers(0, n_games, n_players).astype(np.int32)
        self.scores = rng.random(n_players, dtype=np.float32)
        self.heartbeats = np.zeros(n_players, np.int64)
        self.updates = np.zeros(n_games, np.int64)
        self.total_score = np.zeros(n_games, np.float64)

    def rounds(self, k: int) -> None:
        """Every player heartbeats ``k`` times."""
        self.heartbeats += k
        self.updates += k * np.bincount(self.games,
                                        minlength=len(self.updates))
        self.total_score += k * np.bincount(
            self.games, weights=self.scores, minlength=len(self.updates))

    def one(self, player: int, game: int, score: float) -> None:
        self.heartbeats[player] += 1
        self.updates[game] += 1
        self.total_score[game] += score


def _column(engine, type_name: str, field: str, keys: np.ndarray):
    arena = engine.arena_for(type_name)
    rows, found = arena.lookup_rows(keys)
    if not found.all():
        raise AssertionError(f"{type_name}: {int((~found).sum())} of "
                             f"{len(keys)} keys not active")
    return np.asarray(arena.state[field])[rows]


def check_presence(engine, replay: Replay) -> float:
    """Compare the engine's Presence state with the replay; returns the
    largest float error as a share of its bound."""
    beats = _column(engine, "PresenceGrain", "heartbeats", replay.players)
    if not np.array_equal(beats, replay.heartbeats):
        bad = np.nonzero(beats != replay.heartbeats)[0]
        raise AssertionError(f"heartbeats differ at {len(bad)} players, "
                             f"first {bad[:5]}: {beats[bad[:5]]} != "
                             f"{replay.heartbeats[bad[:5]]}")
    games = np.arange(len(replay.updates), dtype=np.int64)
    updates = _column(engine, "GameGrain", "updates", games)
    if not np.array_equal(updates, replay.updates):
        bad = np.nonzero(updates != replay.updates)[0]
        raise AssertionError(f"updates differ at {len(bad)} games, "
                             f"first {bad[:5]}")
    score = _column(engine, "GameGrain", "total_score", games)
    # scores are in [0, 1), so the sum of |x| is the sum itself
    bound = np.maximum(replay.updates, 1) * F32_U * replay.total_score
    err = np.abs(score.astype(np.float64) - replay.total_score)
    if not (err <= bound).all():
        g = int(np.argmax(err - bound))
        raise AssertionError(f"total_score of game {g}: {score[g]} vs "
                             f"{replay.total_score[g]} (bound {bound[g]})")
    return float((err / np.maximum(bound, 1e-30)).max())


class Reporter:
    """Prints one line per phase; on the chip, a device without memory
    stats fails the phase."""

    def __init__(self, require_memory_stats: bool,
                 out: Callable[[str], None] = print) -> None:
        self.require_memory_stats = require_memory_stats
        self.out = out
        self.lines: list = []
        self.cache_hits = 0

    def phase(self, name: str, t0: float, engine, **extra) -> None:
        wall = time.perf_counter() - t0
        stats = engine.memledger.device_stats()
        if stats is None and self.require_memory_stats:
            raise AssertionError(
                f"phase {name}: device exposes no memory_stats()")
        peak = stats.get("peak_bytes_in_use") if stats else None
        fields = {"phase": name, "wall_s": round(wall, 6),
                  "compiles": engine.compile_count(),
                  "peak_bytes_in_use": peak,
                  "cache_hits": self.cache_hits, **extra}
        self.lines.append(fields)
        self.out(" ".join(f"{k}={v}" for k, v in fields.items()))


async def _flush(engine) -> None:
    import jax

    await engine.flush()
    jax.block_until_ready([a.state for a in engine.arenas.values()])


async def silo_phases(silo, factory, n_players: int, n_games: int,
                      seed: int, rep: Reporter) -> None:
    """Everything after boot, on the silo's own engine."""
    import jax.numpy as jnp

    from orleans_tpu.tensor.engine import MISS_BUF
    from samples.helloworld import IHello
    from samples.presence import run_presence_load, run_presence_load_fused

    engine = silo.tensor_engine
    t0 = time.perf_counter()
    greetings = [f"smoke-{i}" for i in range(4)]
    replies = await asyncio.gather(*(
        factory.get_grain(IHello, 7000 + i).say_hello(g)
        for i, g in enumerate(greetings)))
    want = [f"You said: '{g}', I say: Hello!" for g in greetings]
    if replies != want:
        raise AssertionError(f"say_hello replies {replies} != {want}")
    rep.phase("hello", t0, engine, calls=len(replies))

    replay = Replay(n_players, n_games, seed)

    # cold activation: every player is unseen, so device-key resolution
    # misses and the miss path activates MISS_BUF unique keys per pass
    t0 = time.perf_counter()
    passes0 = engine.activation_passes
    engine.send_batch("PresenceGrain", "heartbeat",
                      jnp.asarray(replay.players.astype(np.int32)),
                      {"game": jnp.asarray(replay.games),
                       "score": jnp.asarray(replay.scores),
                       "tick": jnp.ones(n_players, jnp.int32)})
    await _flush(engine)
    replay.rounds(1)
    live = engine.arena_for("PresenceGrain").live_count
    if live != n_players:
        raise AssertionError(f"{live} players active, want {n_players}")
    passes = engine.activation_passes - passes0
    if passes < -(-n_players // MISS_BUF):
        raise AssertionError(f"{passes} activation passes for "
                             f"{n_players} cold keys (MISS_BUF {MISS_BUF})")
    rep.phase("cold_activate", t0, engine, activation_passes=passes,
              float_err_of_bound=check_presence(engine, replay))

    ticks = 3
    t0 = time.perf_counter()
    fusion_ticks = engine.config.auto_fusion_ticks
    engine.config.auto_fusion_ticks = 0  # unfused: no fusion detection
    await run_presence_load(engine, n_players=n_players,
                                    n_games=n_games, n_ticks=ticks,
                                    seed=seed)
    engine.config.auto_fusion_ticks = fusion_ticks
    await _flush(engine)
    replay.rounds(ticks)
    rep.phase("unfused", t0, engine, ticks=ticks,
              float_err_of_bound=check_presence(engine, replay))

    t0 = time.perf_counter()
    stats = await run_presence_load_fused(engine, n_players=n_players,
                                          n_games=n_games, n_ticks=4,
                                          window=4, seed=seed)
    await _flush(engine)
    if stats["misses"] != 0:
        raise AssertionError(f"fused window missed {stats['misses']}")
    replay.rounds(stats["warm_ticks"] + stats["ticks"])
    rep.phase("fused", t0, engine,
              ticks=stats["warm_ticks"] + stats["ticks"],
              misses=stats["misses"],
              float_err_of_bound=check_presence(engine, replay))

    t0 = time.perf_counter()
    cfg = engine.config
    cfg.auto_fusion_ticks, cfg.auto_fusion_window = 4, 4
    warm, ticks = 8, 8
    stats = await run_presence_load(engine, n_players=n_players,
                                    n_games=n_games, n_ticks=ticks,
                                    seed=seed, warm_ticks=warm)
    await _flush(engine)
    fused_ticks = stats["autofuse"]["ticks_fused"]
    if fused_ticks <= 0:
        raise AssertionError(f"autofusion never engaged: "
                             f"{stats['autofuse']}")
    replay.rounds(warm + ticks)
    rep.phase("autofused", t0, engine, ticks=warm + ticks,
              ticks_fused=fused_ticks,
              float_err_of_bound=check_presence(engine, replay))

    # heartbeats as users call vector grains: through grain references
    t0 = time.perf_counter()
    picks = np.random.default_rng(seed + 1).choice(
        n_players, size=min(8, n_players), replace=False)
    for p in picks.tolist():
        g, s = int(replay.games[p]), float(replay.scores[p])
        await factory.get_grain("PresenceGrain", p).heartbeat(
            {"game": np.int32(g), "score": np.float32(s),
             "tick": np.int32(engine.tick_number + 1)})
        replay.one(p, g, s)
    await _flush(engine)
    rep.phase("client_heartbeat", t0, engine, calls=len(picks),
              float_err_of_bound=check_presence(engine, replay))


async def run_single_chip(n_players: int, n_games: int, seed: int,
                          rep: Reporter) -> None:
    from orleans_tpu.providers.memory_storage import MemoryStorage
    from orleans_tpu.runtime.silo import Silo

    t0 = time.perf_counter()
    silo = Silo(name="chip-smoke",
                storage_providers={"Default": MemoryStorage()})
    await silo.start()
    try:
        factory = silo.attach_client()
        rep.phase("silo_boot", t0, silo.tensor_engine)
        await silo_phases(silo, factory, n_players, n_games, seed, rep)
    finally:
        await silo.stop()


async def _presence_on(engine, n_players: int, n_games: int, seed: int
                       ) -> None:
    from samples.presence import run_presence_load, run_presence_load_fused

    engine.config.auto_fusion_ticks = 0
    await run_presence_load(engine, n_players=n_players, n_games=n_games,
                            n_ticks=3, seed=seed)
    await run_presence_load_fused(engine, n_players=n_players,
                                  n_games=n_games, n_ticks=2, window=2,
                                  seed=seed)
    await _flush(engine)


async def run_mesh(n_players: int, n_games: int, seed: int, n_devices: int,
                   rep: Reporter, structured: Optional[str] = None) -> None:
    """The sharded arena on ``n_devices``: the Presence load on a mesh
    engine must leave the state a one-device engine leaves, with every
    device holding live rows of every arena and the structured exchange
    carrying the cross-shard emits."""
    import jax
    from jax.sharding import Mesh

    from orleans_tpu.tensor.engine import TensorEngine

    devices = jax.devices()[:n_devices]
    if len(devices) != n_devices:
        raise AssertionError(f"{len(devices)} devices, want {n_devices}")

    t0 = time.perf_counter()
    single = TensorEngine()
    await _presence_on(single, n_players, n_games, seed)
    rep.phase("one_device", t0, single)

    t0 = time.perf_counter()
    mesh = Mesh(np.array(devices), ("grains",))
    sharded = TensorEngine(mesh=mesh)
    if structured is not None:
        sharded.config.exchange_structured = structured
    await _presence_on(sharded, n_players, n_games, seed)

    players = np.arange(n_players, dtype=np.int64)
    games = np.arange(n_games, dtype=np.int64)
    for type_name, keys, fields in (
            ("PresenceGrain", players,
             ("heartbeats", "last_heartbeat", "game")),
            ("GameGrain", games, ("updates",))):
        for f in fields:
            a = _column(single, type_name, f, keys)
            b = _column(sharded, type_name, f, keys)
            if not np.array_equal(a, b):
                raise AssertionError(f"{type_name}.{f}: mesh state differs "
                                     f"at {int((a != b).sum())} keys")
    updates = _column(single, "GameGrain", "updates", games)
    a = _column(single, "GameGrain", "total_score", games)
    b = _column(sharded, "GameGrain", "total_score", games)
    # both are float32 sums of the same terms in different orders: each
    # is within the recursive-summation bound of the exact sum
    bound = 2 * np.maximum(updates, 1) * F32_U * np.abs(a).astype(np.float64)
    if not (np.abs(a.astype(np.float64) - b) <= bound).all():
        raise AssertionError("GameGrain.total_score: mesh state differs "
                             "beyond the float32 bound")

    live_per_device = {}
    for type_name, col in (("PresenceGrain", "heartbeats"),
                           ("GameGrain", "updates")):
        shards = sharded.arena_for(type_name).state[col].addressable_shards
        held = {s.device: int(np.count_nonzero(np.asarray(s.data)))
                for s in shards}
        if set(held) != set(devices) or min(held.values()) == 0:
            raise AssertionError(f"{type_name}: live rows per device "
                                 f"{held}")
        live_per_device[type_name] = [held[d] for d in devices]
    ex = sharded.exchange
    if ex is None or not ex.engaged() or ex.exchanges_run == 0:
        raise AssertionError("structured exchange did not engage: "
                             f"{None if ex is None else ex.snapshot()}")
    if ex.cross_shard_msgs <= 0:
        raise AssertionError("no cross-shard messages were exchanged")
    rep.phase("mesh", t0, sharded, devices=n_devices,
              live_rows_per_device=json.dumps(live_per_device,
                                              separators=(",", ":")),
              exchanges_run=ex.exchanges_run,
              cross_shard_msgs=ex.cross_shard_msgs)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the sharded-arena phase on a "
                             "4-chip mesh")
    args = parser.parse_args(argv)

    from orleans_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s) are visible", file=sys.stderr)
        return 1
    rep = Reporter(require_memory_stats=True)

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            rep.cache_hits += 1

    jax.monitoring.register_event_listener(on_event)
    print(f"device platform={platform} kind={devices[0].device_kind} "
          f"count={len(devices)} seed={args.seed} players={N_PLAYERS} "
          f"games={N_GAMES} chips={args.chips}", flush=True)
    if args.chips == 4:
        asyncio.run(run_mesh(N_PLAYERS, N_GAMES, args.seed, 4, rep))
    else:
        asyncio.run(run_single_chip(N_PLAYERS, N_GAMES, args.seed, rep))
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
