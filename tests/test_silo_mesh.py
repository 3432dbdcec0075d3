"""A silo whose engine spans several local devices (``tensor.mesh_devices``).

``orleans_tpu.host.build_silo`` with ``mesh_devices: 4`` builds the
engine over a 4-device ``grains`` mesh (the suite's forced CPU devices):
the arenas are sharded and a heartbeat's game update that lands on
another shard takes the device exchange.  Heartbeat slabs sent by a
``GrainClient`` over the silo's TCP gateway must leave the state a plain
NumPy replay gives.
"""

import asyncio

import numpy as np
import pytest

import samples.presence  # noqa: F401  (registers PresenceGrain/GameGrain)
from orleans_tpu.client import GrainClient
from orleans_tpu.host import build_silo

PLAYERS = 4096
GAMES = 40
SLAB = 1024
#: unit roundoff of float32: the per-term factor of the summation bound
F32_U = 2.0 ** -24


def _silo(tensor: dict):
    return build_silo({"name": "mesh", "host": "127.0.0.1",
                       "storage": {"Default": {"kind": "memory"}},
                       "default_stats_log": False,
                       "silo": {"tensor": tensor}})


def _column(engine, type_name, field, keys):
    arena = engine.arena_for(type_name)
    rows, found = arena.lookup_rows(keys)
    assert found.all(), f"{type_name}: {int((~found).sum())} keys inactive"
    return np.asarray(arena.state[field])[rows]


@pytest.mark.parametrize("structured", ["always", "auto"])
def test_mesh_silo_serves_slabs_like_the_replay(run, structured):
    rng = np.random.default_rng(25)
    game = rng.integers(0, GAMES, PLAYERS).astype(np.int32)
    score = rng.random(PLAYERS, dtype=np.float32)
    order = rng.permutation(PLAYERS)
    slabs = [order[(i * SLAB + np.arange(SLAB)) % PLAYERS]
             for i in range(10)]

    async def main():
        silo = _silo({"mesh_devices": 4, "exchange_structured": structured})
        await silo.start()
        try:
            engine = silo.tensor_engine
            assert engine.n_shards == 4 and engine.exchange is not None
            client = await GrainClient().connect(
                ("127.0.0.1", silo.gateway_port))
            try:
                futs = [client.send_batch(
                    "PresenceGrain", "heartbeat", p.astype(np.int64),
                    {"game": game[p], "score": score[p],
                     "tick": np.full(SLAB, i + 1, np.int32)},
                    want_results=True) for i, p in enumerate(slabs)]
                replies = await asyncio.wait_for(asyncio.gather(*futs), 120)
            finally:
                await client.close()
            assert replies == [None] * len(slabs)
            await engine.flush()
            await engine.wait_completion()
            players = np.arange(PLAYERS, dtype=np.int64)
            games = np.arange(GAMES, dtype=np.int64)
            got = {f: _column(engine, "PresenceGrain", f, players)
                   for f in ("heartbeats", "game", "last_heartbeat")}
            got.update({f: _column(engine, "GameGrain", f, games)
                        for f in ("updates", "total_score")})
            shards = engine.arena_for("GameGrain").state[
                "updates"].sharding.device_set
            snap = silo.collect_metrics()
            names = {f.__name__ for f in engine.exchange._jit_cache.values()}
            return got, len(shards), snap, names
        finally:
            await silo.stop()

    got, n_devices, snap, names = run(main())

    beats = np.zeros(PLAYERS, np.int64)
    last = np.zeros(PLAYERS, np.int64)
    for i, p in enumerate(slabs):
        beats[p] += 1
        last[p] = i + 1
    updates = np.bincount(game, weights=beats, minlength=GAMES)
    exact = np.bincount(game, weights=beats * score.astype(np.float64),
                        minlength=GAMES)
    assert n_devices == 4
    np.testing.assert_array_equal(got["heartbeats"], beats)
    np.testing.assert_array_equal(got["game"], np.where(beats > 0, game, -1))
    np.testing.assert_array_equal(got["last_heartbeat"], last)
    np.testing.assert_array_equal(got["updates"], updates)
    # a float32 sum of k terms in any order: within k * 2**-24 * sum|x|
    bound = np.maximum(updates, 1) * F32_U * exact
    assert (np.abs(got["total_score"] - exact) <= bound).all()

    counters, gauges = snap["counters"], snap["gauges"]
    assert gauges["tensor.shards"][""]["mesh"] == 4.0
    for name in ("route.cross_shard_msgs", "route.delivered_msgs",
                 "route.exchanges", "route.exchange_dropped"):
        assert name in counters, name
    if structured == "always":
        # the device exchange carried the game updates between shards
        assert counters["route.cross_shard_msgs"][""] > 0
        assert counters["route.exchanges"][""] > 0
        assert names == {"_exchange_kernel"}
    else:
        # a CPU mesh keeps the exchange disengaged: measure-only probes
        assert names <= {"_exchange_probe"}


def test_one_device_builds_no_mesh(run):
    async def main():
        silo = _silo({"mesh_devices": 1})
        await silo.start()
        try:
            engine = silo.tensor_engine
            snap = silo.collect_metrics()
            return engine.mesh, engine.exchange, engine.n_shards, snap
        finally:
            await silo.stop()

    mesh, exchange, n_shards, snap = run(main())
    assert mesh is None and exchange is None and n_shards == 1
    assert snap["gauges"]["tensor.shards"][""]["mesh"] == 1.0
    assert "route.cross_shard_msgs" not in snap["counters"]


@pytest.mark.parametrize("want", [64, 0])
def test_unservable_device_count_refused_at_construction(want):
    import jax

    with pytest.raises(ValueError, match=str(want)) as err:
        _silo({"mesh_devices": want})
    if want > 1:
        assert str(len(jax.local_devices())) in str(err.value)
