"""Multi-device (8-way virtual CPU mesh) data-plane tests.

These run on the conftest-forced 8-device host platform and exercise the
REAL shardings the TPU path uses: grain-state rows sharded over the
'grains' mesh axis (the ring-partition analog — reference:
src/OrleansRuntime/ConsistentRing/VirtualBucketsRingProvider.cs:38), the
directory mirror replicated, emits routed across shard boundaries on
device.
"""

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from orleans_tpu.tensor import TensorEngine
from orleans_tpu.tensor.arena import _hash_keys_u64

from samples.presence import run_presence_load
import tests.test_tensor_engine  # noqa: F401 — registers AccumGrain


N_DEV = 8


def _mesh() -> Mesh:
    devices = jax.devices("cpu")
    assert len(devices) >= N_DEV, "conftest must force 8 host devices"
    return Mesh(np.array(devices[:N_DEV]), ("grains",))


def _make_engine(**kw) -> TensorEngine:
    return TensorEngine(mesh=_mesh(), **kw)


def test_sharded_arena_blocks_and_placement():
    """Rows land in the shard block their key hashes to, and state columns
    carry the mesh sharding (one block per device)."""
    engine = _make_engine(initial_capacity=16 * N_DEV)
    arena = engine.arena_for("AccumGrain")
    assert arena.n_shards == N_DEV

    keys = np.arange(100, dtype=np.int64)
    rows = arena.resolve_rows(keys)
    shards = rows // arena.shard_capacity
    expected = (_hash_keys_u64(keys) % np.uint64(N_DEV)).astype(np.int64)
    np.testing.assert_array_equal(shards, expected)

    col = arena.state["total"]
    assert isinstance(col.sharding, NamedSharding)
    assert col.sharding.spec == PartitionSpec("grains")
    # each device holds exactly one contiguous shard block
    assert len(col.sharding.device_set) == N_DEV


def test_cross_shard_emit_routing(run):
    """Presence over the mesh: player heartbeats (sharded by player key)
    emit game updates whose destination rows live on OTHER shards — the
    device-side directory mirror must route them without host help."""

    async def main():
        engine = _make_engine(initial_capacity=32 * N_DEV)
        n_players, n_games, n_ticks = 16 * N_DEV, N_DEV, 3
        stats = await run_presence_load(engine, n_players=n_players,
                                        n_games=n_games, n_ticks=n_ticks)
        assert stats["messages"] == 2 * n_players * n_ticks
        game = engine.arena_for("GameGrain")
        assert game.live_count == n_games
        total = sum(int(game.read_row(g)["updates"]) for g in range(n_games))
        assert total == n_players * n_ticks
        # games are themselves spread over shards (cross-shard edges exist)
        grows = game.resolve_rows(np.arange(n_games, dtype=np.int64))
        assert len(set((grows // game.shard_capacity).tolist())) > 1

    run(main())


def test_growth_repack_preserves_state_under_sharding(run):
    """Arena growth doubles every shard block and repacks rows; state must
    survive with the same sharding spec (the reshard-in-miniature)."""

    async def main():
        engine = _make_engine(initial_capacity=N_DEV)  # 1 row/shard: tiny
        engine.send_batch("AccumGrain", "add",
                          np.arange(4, dtype=np.int64),
                          {"v": np.full(4, 2.5, np.float32)})
        await engine.flush()
        arena = engine.arena_for("AccumGrain")
        gen0 = arena.generation
        arena.resolve_rows(np.arange(100, 200, dtype=np.int64))  # forces growth
        assert arena.generation > gen0
        for k in range(4):
            assert float(arena.read_row(k)["total"]) == 2.5
        col = arena.state["total"]
        assert col.sharding.spec == PartitionSpec("grains")
        assert col.shape[0] == arena.capacity

    run(main())


def test_injector_survives_repack_on_mesh(run):
    """A cached-destination injector whose rows went stale via growth must
    re-resolve, not scatter into the wrong shard blocks."""

    async def main():
        engine = _make_engine(initial_capacity=N_DEV)
        keys = np.arange(6, dtype=np.int64)
        inj = engine.make_injector("AccumGrain", "add", keys)
        inj.inject({"v": np.ones(6, np.float32)})
        await engine.flush()
        arena = engine.arena_for("AccumGrain")
        arena.resolve_rows(np.arange(50, 120, dtype=np.int64))  # repack
        inj.inject({"v": np.ones(6, np.float32)})
        await engine.flush()
        for k in range(6):
            assert float(arena.read_row(k)["total"]) == 2.0

    run(main())


def test_dryrun_entrypoint_runs_in_suite():
    """The driver's multi-chip dry run must pass in-process on the virtual
    mesh."""
    import __graft_entry__

    __graft_entry__.dryrun_multichip(N_DEV)


def test_fanout_over_mesh(run):
    """Chirper's CSR fan-out on an 8-device mesh: publishes from rows
    sharded across devices expand into follower deliveries that land on
    OTHER shards, exactly matching the adjacency — the ragged-scatter
    path must be mesh-correct, not just single-device-correct."""

    async def main():
        from samples.chirper import build_follow_graph, run_chirper_load

        engine = _make_engine(initial_capacity=64 * N_DEV)
        fan = build_follow_graph(300, mean_followers=10.0, seed=11)
        await run_chirper_load(engine, n_accounts=300, n_ticks=2,
                               fanout=fan)
        arena = engine.arena_for("ChirperAccount")
        assert arena.n_shards == N_DEV
        received = np.asarray(arena.state["received"])
        rows = arena.resolve_rows(np.arange(300, dtype=np.int64))
        followers_of = np.zeros(300, np.int64)
        for s in range(300):
            for d in fan.followers_of(s):
                followers_of[d] += 1
        np.testing.assert_array_equal(received[rows], 2 * followers_of)
        # rows really are spread across shards (cross-shard deliveries
        # happened: at least 2 shards held followers)
        shards = set((rows // arena.shard_capacity).tolist())
        assert len(shards) >= 2, shards

    run(main())


def test_gps_and_twitter_over_mesh(run):
    """The other two benchmark workloads execute correctly sharded."""

    async def main():
        from samples.gpstracker import run_gps_load
        from samples.twitter_sentiment import run_twitter_load

        e1 = _make_engine(initial_capacity=64 * N_DEV)
        stats = await run_gps_load(e1, n_devices=400, n_ticks=3,
                                   move_fraction=0.5, seed=2)
        notif = e1.arena_for("PushNotifierGrain")
        assert int(np.asarray(notif.state["forwarded"]).sum()) \
            == stats["notified"]

        e2 = _make_engine(initial_capacity=64 * N_DEV)
        await run_twitter_load(e2, n_tweets_per_tick=500, n_hashtags=40,
                               n_ticks=2)
        arena = e2.arena_for("HashtagGrain")
        assert int(np.asarray(arena.state["total"]).sum()) == 500 * 2 * 2

    run(main())


def test_fused_window_over_mesh(run):
    """Tick fusion on the 8-device mesh: a fused window over SHARDED
    arena state produces the same results as the unfused mesh engine."""

    async def main():
        from samples.presence import (
            run_presence_load,
            run_presence_load_fused,
        )

        n_players, n_games, T = 800, 8, 4
        e1 = _make_engine(initial_capacity=16 * N_DEV)
        await run_presence_load(e1, n_players=n_players, n_games=n_games,
                                n_ticks=T)
        a1 = e1.arena_for("GameGrain")
        rows1 = a1.resolve_rows(np.arange(n_games, dtype=np.int64))
        ref = np.asarray(a1.state["updates"])[rows1]

        e2 = _make_engine(initial_capacity=16 * N_DEV)
        stats = await run_presence_load_fused(
            e2, n_players=n_players, n_games=n_games, n_ticks=T, window=2,
            seed=0)
        a2 = e2.arena_for("GameGrain")
        rows2 = a2.resolve_rows(np.arange(n_games, dtype=np.int64))
        got = np.asarray(a2.state["updates"])[rows2]
        total2 = stats["ticks"] + 2  # + warm window
        np.testing.assert_allclose(got / total2, ref / T)

    run(main())


def test_fused_after_reshard(run):
    """Elasticity + fusion: resharding the engine (mesh change) between
    windows forces a rebuild and the next window stays exact."""

    async def main():
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from samples.presence import PresenceGrain  # noqa: F401

        engine = _make_engine(initial_capacity=16 * N_DEV)
        players = np.arange(200, dtype=np.int64)
        engine.arena_for("PresenceGrain").resolve_rows(players)
        engine.arena_for("GameGrain").resolve_rows(
            np.arange(4, dtype=np.int64))
        prog = engine.fuse_ticks("PresenceGrain", "heartbeat", players)
        static = {"game": jnp.zeros(200, jnp.int32),
                  "score": jnp.ones(200, jnp.float32)}
        prog.run({"tick": jnp.arange(1, 3, dtype=jnp.int32)},
                 static_args=static)
        assert prog.verify() == 0

        # shrink the mesh 8 -> 4 devices (a "silo group" leaving)
        devices = jax.devices("cpu")[:4]
        await engine.reshard(Mesh(np.array(devices), ("grains",)))
        assert engine.n_shards == 4

        prog.run({"tick": jnp.arange(3, 5, dtype=jnp.int32)},
                 static_args=static)
        assert prog.verify() == 0
        arena = engine.arena_for("PresenceGrain")
        rows = arena.resolve_rows(players)
        hb = np.asarray(arena.state["heartbeats"])[rows]
        np.testing.assert_array_equal(hb, 4)

    run(main())
