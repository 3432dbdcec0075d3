"""Benchmark-workload samples: Chirper, GPSTracker, TwitterSentiment.

These are the three BASELINE.json configs beyond HelloWorld/Presence.
Each test checks the vector-grain implementation against an exact
host-side (numpy/dict) oracle of the reference semantics:
Chirper's follower fan-out (ChirperAccount.cs:129-156), GPSTracker's
movement gate + speed (DeviceGrain.cs:37), TwitterSentiment's
per-hashtag scoring + first-activation counting (HashtagGrain.cs:70).
"""

import numpy as np
import pytest

from orleans_tpu.tensor import DeviceFanout, FanoutOverflowError, TensorEngine
from orleans_tpu.tensor.fanout import KEY_SENTINEL

from samples.chirper import (
    ChirperAccount,
    build_follow_graph,
    run_chirper_load,
)
from samples.gpstracker import (
    N_NOTIFIERS,
    DeviceGrain,
    PushNotifierGrain,
    run_gps_load,
)
from samples.twitter_sentiment import (
    TweetCounterGrain,
    HashtagGrain,
    flatten_tweets,
    hashtag_key,
    run_twitter_load,
)


# ---------------------------------------------------------------------------
# DeviceFanout (the ragged-expansion primitive)
# ---------------------------------------------------------------------------

def test_fanout_expansion_matches_adjacency():
    import jax.numpy as jnp

    fan = DeviceFanout(budget=64)
    adj = {1: [10, 11, 12], 2: [20], 5: [50, 51]}
    for s, ds in adj.items():
        for d in ds:
            fan.follow(s, d)

    src = jnp.asarray(np.array([2, 1, 7, 5], np.int32))  # 7 has no followers
    args = {"v": jnp.asarray(np.array([200, 100, 700, 500], np.int32))}
    dst, gargs, valid = fan.expand(src, args)
    dst, v, sk, valid = (np.asarray(dst), np.asarray(gargs["v"]),
                         np.asarray(gargs["src_key"]), np.asarray(valid))
    got = sorted(zip(dst[valid].tolist(), v[valid].tolist(),
                     sk[valid].tolist()))
    want = sorted([(20, 200, 2), (10, 100, 1), (11, 100, 1), (12, 100, 1),
                   (50, 500, 5), (51, 500, 5)])
    assert got == want
    assert (dst[~valid] == KEY_SENTINEL).all()
    assert fan.overflow_check() == 0  # nothing overflowed: no parked lanes


def test_fanout_mutation_and_empty_graph():
    import jax.numpy as jnp

    fan = DeviceFanout(budget=16)
    src = jnp.asarray(np.array([3], np.int32))
    dst, _, valid = fan.expand(src, {"v": jnp.zeros(1)})
    assert not np.asarray(valid).any()          # empty graph: no expansion

    fan.follow(3, 9)
    dst, _, valid = fan.expand(src, {"v": jnp.zeros(1)})
    assert np.asarray(dst)[np.asarray(valid)].tolist() == [9]

    fan.unfollow(3, 9)                          # mirror rebuilds lazily
    dst, _, valid = fan.expand(src, {"v": jnp.zeros(1)})
    assert not np.asarray(valid).any()


def test_fanout_overflow_parks_lanes_not_raises():
    """Per-round expansion overflow is a PARK event now, never a
    mid-tick error (the ShardExchange contract): the overflowing source
    lane delivers NOTHING this round (all-or-nothing — a partial prefix
    would double-deliver on redelivery) and comes back as a device-side
    dropped mask; only the storage budget (too many EDGES) still raises
    at rebuild."""
    import jax.numpy as jnp

    fan = DeviceFanout(budget=4)
    for d in range(3):
        fan.follow(1, 100 + d)
    # two publishes from key 1 in one round: 6 expansions > width 4 —
    # the FIRST lane's 3 slots fit, the second lane parks whole
    src = jnp.asarray(np.array([1, 1], np.int32))
    dst, _gargs, valid = fan.expand(src, {"v": jnp.zeros(2)})
    n_dropped, dropped = fan.take_drop()
    assert int(n_dropped) == 1
    assert np.asarray(dropped).tolist() == [False, True]
    # the completed lane delivered ALL its slots, the parked one none
    assert sorted(np.asarray(dst)[np.asarray(valid)].tolist()) \
        == [100, 101, 102]
    # re-expanding exactly the parked lanes completes the delivery
    dst2, _g2, valid2 = fan.expand(src, {"v": jnp.zeros(2)},
                                   jnp.asarray(np.array(dropped)))
    n2, _d2 = fan.take_drop()
    assert int(n2) == 0
    assert sorted(np.asarray(dst2)[np.asarray(valid2)].tolist()) \
        == [100, 101, 102]
    assert fan.overflow_check() == 0  # both drops were taken

    # the STORAGE budget stays a hard error
    over = DeviceFanout(budget=2)
    for d in range(3):
        over.follow(1, 200 + d)
    with pytest.raises(FanoutOverflowError):
        over.expand(jnp.asarray(np.array([1], np.int32)),
                    {"v": jnp.zeros(1)})


def _triples(dst, gargs, valid):
    dst, valid = np.asarray(dst), np.asarray(valid)
    return sorted(zip(dst[valid].tolist(),
                      np.asarray(gargs["src_key"])[valid].tolist(),
                      np.asarray(gargs["v"])[valid].tolist()))


def test_fanout_sized_expansion_matches_full_width():
    """A round sized from its host keys expands at the ladder rung at or
    above its exact degree sum and delivers the same (dst, src_key,
    args) triples as the full-CSR-width expansion."""
    import jax.numpy as jnp

    from orleans_tpu.tensor.exchange import ladder_ceil

    fan = build_follow_graph(400, mean_followers=8.0, seed=5)
    rng = np.random.default_rng(7)
    # duplicates, and keys past the accounts (no followers): need 0 each
    keys = np.concatenate([rng.integers(0, 400, 48), [5, 5, 401, 9999]])
    src = jnp.asarray(keys.astype(np.int32))
    args = {"v": jnp.asarray(np.arange(len(keys), dtype=np.int32) * 3)}
    deg = np.asarray([len(fan.followers_of(int(k))) for k in keys])
    need = fan.need(keys)
    assert need == int(deg.sum())

    full = fan.expand(src, args)
    full_width = fan.width
    assert fan.take_drop()[0] == 0
    sized = fan.expand(src, args, keys_host=keys)
    assert fan.take_drop()[0] == 0
    assert fan.width == max(256, ladder_ceil(need)) < full_width
    assert np.asarray(sized[0]).shape == (fan.width,)
    assert _triples(*sized) == _triples(*full)
    assert len(_triples(*sized)) == need
    assert (fan.sized_rounds, fan.full_width_rounds) == (1, 1)
    assert (fan.lanes_needed, fan.lanes_expanded) == (need, fan.width)


@pytest.mark.parametrize("mutate", ["follow", "unfollow", "add_edges"])
def test_fanout_sized_width_high_water_mark(mutate):
    """A sized round never expands narrower than the widest rung since
    the last rebuild; a graph change resets the mark."""
    import jax.numpy as jnp

    fan = DeviceFanout()
    fan.add_edges(np.full(300, 1), np.arange(1000, 1300))
    fan.add_edges(np.full(5, 2), np.arange(2000, 2005))

    def round_width(key):
        keys = np.array([key], np.int64)
        fan.expand(jnp.asarray(keys.astype(np.int32)),
                   {"v": jnp.zeros(1, jnp.int32)}, keys_host=keys)
        assert int(fan.take_drop()[0]) == 0
        return fan.width

    assert round_width(2) == 256
    assert round_width(1) == 384            # ladder_ceil(300)
    assert round_width(2) == 384            # the mark holds
    assert round_width(1) == 384
    if mutate == "follow":
        fan.follow(2, 2005)
    elif mutate == "unfollow":
        fan.unfollow(1, 1000)
    else:
        fan.add_edges(np.array([2]), np.array([2006]))
    assert round_width(2) == 256            # rebuilt: the mark reset


def test_fanout_sized_round_capped_at_csr_width_parks():
    """Duplicate host keys whose need passes the CSR width expand at the
    CSR width and park the lane that does not fit, exactly like the
    full-width overflow; the redelivery completes it."""
    import jax.numpy as jnp

    fan = DeviceFanout()
    fan.add_edges(np.full(300, 1), np.arange(100, 400))   # CSR width 512
    keys = np.array([1, 1], np.int64)
    src = jnp.asarray(keys.astype(np.int32))
    assert fan.need(keys) == 600
    dst, _g, valid = fan.expand(src, {"v": jnp.zeros(2)}, keys_host=keys)
    assert fan.width == 512
    n_dropped, dropped = fan.take_drop()
    assert int(n_dropped) == 1
    assert np.asarray(dropped).tolist() == [False, True]
    assert sorted(np.asarray(dst)[np.asarray(valid)].tolist()) \
        == list(range(100, 400))
    dst2, _g2, valid2 = fan.expand(src, {"v": jnp.zeros(2)},
                                   jnp.asarray(np.array(dropped)))
    assert int(fan.take_drop()[0]) == 0
    assert sorted(np.asarray(dst2)[np.asarray(valid2)].tolist()) \
        == list(range(100, 400))
    assert (fan.sized_rounds, fan.full_width_rounds) == (1, 1)
    assert fan.overflow_check() == 0


# ---------------------------------------------------------------------------
# Chirper
# ---------------------------------------------------------------------------

def test_chirper_exact_small_graph(run):
    """5 accounts, known graph: received counts / checksums must equal the
    sequential per-follower delivery of the reference."""

    async def main():
        engine = TensorEngine()
        fan = DeviceFanout(budget=64)
        adj = {0: [1, 2, 3], 1: [2], 3: [0, 4]}
        for s, ds in adj.items():
            for d in ds:
                fan.follow(s, d)

        stats = await run_chirper_load(engine, n_accounts=5, n_ticks=3,
                                       fanout=fan)
        arena = engine.arena_for("ChirperAccount")
        received = np.asarray(arena.state["received"])
        rows = arena.resolve_rows(np.arange(5, dtype=np.int64))

        # oracle: per-account fan-in = number of accounts following them
        followers_of = {k: 0 for k in range(5)}
        for s, ds in adj.items():
            for d in ds:
                followers_of[d] += 1
        for acct in range(5):
            assert received[rows[acct]] == 3 * followers_of[acct], acct
        published = np.asarray(arena.state["published"])
        assert all(published[rows[a]] == 3 for a in range(5))
        assert stats["messages"] == 3 * (5 + 6)

    run(main())


def test_chirper_power_law_load(run):
    """Power-law graph at small scale: total deliveries equal edge count
    per tick and the expansion is exact per account."""

    async def main():
        engine = TensorEngine()
        fan = build_follow_graph(200, mean_followers=8.0, seed=3)
        await run_chirper_load(engine, n_accounts=200, n_ticks=2, fanout=fan)
        arena = engine.arena_for("ChirperAccount")
        received = np.asarray(arena.state["received"])
        rows = arena.resolve_rows(np.arange(200, dtype=np.int64))
        followers_of = np.zeros(200, np.int64)
        for s in range(200):
            for d in fan.followers_of(s):
                followers_of[d] += 1
        np.testing.assert_array_equal(received[rows], 2 * followers_of)
        # power-law sanity: the most-followed account dominates the median
        deg = np.asarray([len(fan.followers_of(s)) for s in range(200)])
        assert deg.max() >= 10 * max(1, int(np.median(deg)))

    run(main())


def test_chirper_host_key_slabs_sized_exact(run):
    """Publish slabs handed to the engine as the gateway hands them
    (host keys through ``send_batch``) expand at their degree sum's
    rung, and every follower receives every chirp exactly once."""

    async def main():
        engine = TensorEngine()
        n, lanes, n_slabs = 300, 64, 6
        fan = build_follow_graph(n, mean_followers=8.0, seed=11)
        engine.register_fanout("ChirperAccount", "publish", fan,
                               "ChirperAccount", "new_chirp")
        arena = engine.arena_for("ChirperAccount")
        arena.reserve(n)
        arena.resolve_rows(np.arange(n, dtype=np.int64))

        order = np.random.default_rng(4).permutation(n)
        published = np.zeros(n, np.int64)
        newest = np.full(n, -1, np.int64)
        for i in range(n_slabs):
            keys = order[(i * lanes + np.arange(lanes)) % n].astype(np.int64)
            ids = (i * lanes + np.arange(lanes)).astype(np.int32)
            fut = engine.send_batch("ChirperAccount", "publish", keys,
                                    {"chirp_id": ids}, want_results=True)
            await engine.flush()
            await fut
            published[keys] += 1
            newest[keys] = ids

        # the per-follower oracle
        received = np.zeros(n, np.int64)
        checksum = np.zeros(n, np.int64)
        last = np.full(n, -1, np.int64)
        for s in range(n):
            for d in fan.followers_of(s):
                received[d] += published[s]
                checksum[d] += published[s] * (s % 97)
                if published[s]:
                    last[d] = max(last[d], newest[s])
        rows = arena.resolve_rows(np.arange(n, dtype=np.int64))
        st = {f: np.asarray(arena.state[f])[rows]
              for f in ("published", "received", "last_chirp", "checksum")}
        np.testing.assert_array_equal(st["published"], published)
        np.testing.assert_array_equal(st["received"], received)
        np.testing.assert_array_equal(st["last_chirp"], last)
        np.testing.assert_array_equal(st["checksum"], checksum)
        assert fan.sized_rounds == n_slabs
        assert fan.full_width_rounds == 0
        assert fan.dropped_lanes == 0
        assert fan.lanes_needed == int(received.sum())
        assert fan.width < -(-fan.edge_count // 256) * 256
        assert engine.snapshot()["fanouts"]["ChirperAccount.publish"] \
            == fan.snapshot()

    run(main())


def test_fanout_metrics_collected(run):
    """The silo's metrics collection reports each registered fan-out's
    width and round counters (strict: an undeclared name raises)."""
    from orleans_tpu.config import SiloConfig
    from orleans_tpu.runtime.silo import Silo

    async def main():
        silo = Silo(config=SiloConfig(name="fmetrics"))
        await silo.start()
        try:
            engine = silo.tensor_engine
            fan = DeviceFanout(budget=64)
            fan.add_edges(np.array([1, 1, 2]), np.array([2, 3, 3]))
            engine.register_fanout("ChirperAccount", "publish", fan,
                                   "ChirperAccount", "new_chirp")
            engine.arena_for("ChirperAccount").reserve(4)
            engine.send_batch("ChirperAccount", "publish",
                              np.array([1, 2], np.int64),
                              {"chirp_id": np.array([5, 6], np.int32)})
            await engine.flush()
            snap = silo.collect_metrics()
            for name in ("fanout.sized_rounds", "fanout.full_width_rounds",
                         "fanout.lanes_needed", "fanout.lanes_expanded",
                         "fanout.dropped_lanes", "fanout.redeliveries"):
                assert name in snap["counters"], name
            assert "fanout.width" in snap["gauges"]
            assert (fan.sized_rounds, fan.lanes_needed, fan.width) \
                == (1, 3, 64)
        finally:
            await silo.stop()

    run(main())


# ---------------------------------------------------------------------------
# GPSTracker
# ---------------------------------------------------------------------------

def test_gps_movement_gate_and_speed(run):
    """Only moved devices notify; speed matches the equirectangular
    formula (reference: DeviceGrain.GetSpeed)."""

    async def main():
        import jax.numpy as jnp

        engine = TensorEngine()
        engine.arena_for("DeviceGrain").reserve(4)
        engine.arena_for("PushNotifierGrain").reserve(N_NOTIFIERS)
        devices = np.arange(4, dtype=np.int64)
        inj = engine.make_injector("DeviceGrain", "process_message", devices)

        lat0 = np.array([47.60, 47.61, 47.62, 47.63], np.float32)
        lon0 = np.full(4, -122.1, np.float32)
        base = {"lon": jnp.asarray(lon0),
                "device": jnp.asarray(devices.astype(np.int32))}
        inj.inject({**base, "lat": jnp.asarray(lat0),
                    "ts": jnp.full(4, 1.0, jnp.float32)})
        await engine.flush()

        # second fix: only devices 0 and 2 move (0.001 deg north over 10s)
        lat1 = lat0 + np.array([1e-3, 0, 1e-3, 0], np.float32)
        inj.inject({**base, "lat": jnp.asarray(lat1),
                    "ts": jnp.full(4, 11.0, jnp.float32)})
        await engine.flush()

        dev_arena = engine.arena_for("DeviceGrain")
        rows = dev_arena.resolve_rows(devices)
        moves = np.asarray(dev_arena.state["moves"])[rows]
        np.testing.assert_array_equal(moves, [2, 1, 2, 1])  # first fix counts

        # expected speed: dist = dlat(rad) * R over 10s
        expected = np.deg2rad(1e-3) * 6371000.0 / 10.0
        speed = np.asarray(dev_arena.state["speed"])[rows]
        # float32 keeps ~1e-6 deg resolution at lat 47 — 1e-3 rtol covers it
        np.testing.assert_allclose(speed[[0, 2]], expected, rtol=1e-3)
        np.testing.assert_allclose(speed[[1, 3]], 0.0)

        notif = engine.arena_for("PushNotifierGrain")
        total_forwarded = int(np.asarray(notif.state["forwarded"]).sum())
        assert total_forwarded == 4 + 2  # all first fixes + two moves

    run(main())


def test_gps_load_driver(run):
    async def main():
        engine = TensorEngine()
        stats = await run_gps_load(engine, n_devices=500, n_ticks=4,
                                   move_fraction=0.5, seed=1)
        notif = engine.arena_for("PushNotifierGrain")
        forwarded = int(np.asarray(notif.state["forwarded"]).sum())
        assert forwarded == stats["notified"]
        assert stats["messages"] == 500 * 4 + forwarded

    run(main())


# ---------------------------------------------------------------------------
# TwitterSentiment
# ---------------------------------------------------------------------------

def test_twitter_scoring_exact(run):
    """Sign-split totals and the first-activation counter match the
    reference semantics exactly."""

    async def main():
        engine = TensorEngine()
        engine.arena_for("HashtagGrain").reserve(16)
        engine.arena_for("TweetCounterGrain").reserve(1)

        tweets = [
            {"hashtags": ["jax", "tpu"], "score": 1},
            {"hashtags": ["jax"], "score": -1},
            {"hashtags": ["tpu"], "score": 0},
            {"hashtags": ["jax", "xla"], "score": 1},
        ]
        flat = flatten_tweets(tweets)
        engine.send_batch("HashtagGrain", "add_score", flat["keys"],
                          {"score": flat["scores"]})
        await engine.flush()

        arena = engine.arena_for("HashtagGrain")
        rows = arena.resolve_rows(np.asarray(
            [hashtag_key(t) for t in ("jax", "tpu", "xla")], np.int64))
        total = np.asarray(arena.state["total"])[rows]
        pos = np.asarray(arena.state["positive"])[rows]
        neg = np.asarray(arena.state["negative"])[rows]
        np.testing.assert_array_equal(total, [3, 2, 1])
        np.testing.assert_array_equal(pos, [2, 1, 1])
        np.testing.assert_array_equal(neg, [1, 0, 0])

        counter = engine.arena_for("TweetCounterGrain")
        crow = counter.resolve_rows(np.array([0], np.int64))
        assert int(np.asarray(counter.state["hashtags"])[crow][0]) == 3

        # second wave: old tags don't re-count, a new one does
        engine.send_batch("HashtagGrain", "add_score",
                          np.asarray([hashtag_key("jax"),
                                      hashtag_key("new")], np.int64),
                          {"score": np.asarray([1, -1], np.int32)})
        await engine.flush()
        assert int(np.asarray(counter.state["hashtags"])[crow][0]) == 4

    run(main())


def test_twitter_load_driver(run):
    async def main():
        engine = TensorEngine()
        stats = await run_twitter_load(engine, n_tweets_per_tick=1000,
                                       n_hashtags=50, tags_per_tweet=2,
                                       n_ticks=3)
        arena = engine.arena_for("HashtagGrain")
        total = int(np.asarray(arena.state["total"]).sum())
        assert total == 1000 * 2 * 3
        counter = engine.arena_for("TweetCounterGrain")
        crow = counter.resolve_rows(np.array([0], np.int64))
        counted = int(np.asarray(counter.state["hashtags"])[crow][0])
        assert 0 < counted <= 50
        assert stats["messages"] == (2000 + 1000) * 3

    run(main())


# ---------------------------------------------------------------------------
# Chirper host path (per-message actor parity surface)
# ---------------------------------------------------------------------------

def test_chirper_host_path(run):
    """Follow → publish → per-follower delivery over the asyncio host
    path (reference: ChirperAccount.cs full RPC loop)."""

    async def main():
        from orleans_tpu.runtime.silo import Silo
        from samples.chirper_host import IHostChirperAccount

        silo = Silo(name="chirper-host")
        await silo.start()
        try:
            factory = silo.attach_client()
            a, b, c = (factory.get_grain(IHostChirperAccount, i)
                       for i in (1001, 1002, 1003))
            await b.follow(1001)
            await c.follow(1001)
            await c.follow(1002)
            await a.publish(7)
            await b.publish(8)
            # publish awaits all deliveries (reference WhenAll parity)
            assert await b.received_count() == 1
            assert await c.received_count() == 2
            got = await c.recent_chirps()
            assert sorted(got) == [(7, 1001), (8, 1002)]
        finally:
            await silo.stop()

    run(main())


def test_fanout_no_duplicate_delivery_on_miss_redelivery(run):
    """Publishing from NOT-yet-activated keys via the optimistic device
    path must deliver each chirp to each follower exactly once: the
    miss-check redelivery (which re-runs the publish state update) must
    not re-expand the fan-out."""

    async def main():
        import jax.numpy as jnp

        engine = TensorEngine()
        fan = DeviceFanout(budget=64)
        fan.follow(1, 10)
        fan.follow(1, 11)
        fan.follow(2, 10)
        engine.register_fanout("ChirperAccount", "publish", fan,
                               "ChirperAccount", "new_chirp")
        # no reserve/injector: publisher keys are unseen -> optimistic
        # resolution parks a miss-check and redelivers
        engine.send_batch(
            "ChirperAccount", "publish",
            jnp.asarray(np.array([1, 2], np.int32)),
            {"chirp_id": jnp.asarray(np.array([100, 200], np.int32))})
        await engine.flush()

        arena = engine.arena_for("ChirperAccount")
        rows = arena.resolve_rows(np.array([1, 2, 10, 11], np.int64))
        received = np.asarray(arena.state["received"])[rows]
        published = np.asarray(arena.state["published"])[rows]
        np.testing.assert_array_equal(received, [0, 0, 2, 1])
        np.testing.assert_array_equal(published, [1, 1, 0, 0])

    run(main())


def test_gps_host_path(run):
    """Host-path GPS parity: per-fix RPC with movement-gated notifier
    forward (reference: DeviceGrain.ProcessMessage)."""

    async def main():
        import asyncio as _a

        from orleans_tpu.runtime.silo import Silo
        from samples.gpstracker_host import (
            HostPushNotifierGrain,
            IHostDevice,
            IHostPushNotifier,
        )

        HostPushNotifierGrain.forwarded = 0
        HostPushNotifierGrain.speed_sum = 0.0
        silo = Silo(name="gps-host")
        await silo.start()
        try:
            f = silo.attach_client()
            d = f.get_grain(IHostDevice, 3001)
            await d.process_message(47.60, -122.1, 1.0)   # first fix: moved
            await d.process_message(47.60, -122.1, 2.0)   # unchanged: gated
            await d.process_message(47.601, -122.1, 12.0)  # moved again
            await _a.sleep(0.05)  # one-way forwards drain
            n = f.get_grain(IHostPushNotifier, 0)
            forwarded, speed_sum = await n.totals()
            assert forwarded == 2, forwarded
            # second move: ~0.001 deg over 10s ≈ 11.1 m/s
            assert 10.0 < speed_sum < 13.0, speed_sum
        finally:
            await silo.stop()

    run(main())


def test_presence_pipelined_latency_mode_fused_exact(run):
    """The pipelined latency operating point rides window=1 fused
    programs with DONATED state and event-driven completion (the
    honest 10ms mode).  Exactness: every injected heartbeat lands
    exactly one game update, asserted through both the state columns
    and the device miss counters folded at end of run; honored flags
    are direct observations (no floor fields exist any more)."""

    async def main():
        from samples.presence import run_presence_pipelined

        engine = TensorEngine()
        stats = await run_presence_pipelined(
            engine, n_players=4096, n_games=64, budget=0.05,
            n_ticks=12, warm_ticks=4)
        assert stats["messages"] > 0
        assert stats["tick_p99_seconds"] > 0
        assert stats["mean_batch"] >= 2048
        assert stats["pipeline_depth"] >= 2
        # the floor is gone, not netted out: no sync-floor keys, and
        # honored IS honored_strict
        assert "sync_floor_s" not in stats
        assert stats["honored"] == stats["honored_strict"]
        assert stats["donation_fallbacks"] == 0  # donated path active
        upd = np.asarray(engine.arena_for("GameGrain").state["updates"])
        hb = np.asarray(
            engine.arena_for("PresenceGrain").state["heartbeats"])
        assert int(upd.sum()) == int(hb.sum())  # one update per heartbeat
        # verify() folded the emit deliveries into messages_processed
        assert engine.messages_processed == int(upd.sum()) + int(hb.sum())

    run(main())


def test_twitter_fused_matches_unfused(run):
    """The fused twitter tier (dispatcher pool + per-tick slab args +
    in-window hashtag resolve) must produce byte-identical hashtag and
    counter state to the unfused engine over the same Zipf payloads."""

    async def main():
        from samples.twitter_sentiment import (
            COUNTER_KEY,
            _zipf_payloads,
            run_twitter_load,
            run_twitter_load_fused,
        )

        n_tweets, n_tags, T = 2_000, 300, 8
        plain = TensorEngine()
        await run_twitter_load(plain, n_tweets_per_tick=n_tweets,
                               n_hashtags=n_tags, n_ticks=T,
                               warm_ticks=0, seed=3)
        fused = TensorEngine()
        stats = await run_twitter_load_fused(
            fused, n_tweets_per_tick=n_tweets, n_hashtags=n_tags,
            n_ticks=T, window=4, seed=3)
        assert stats["engine"] == "fused"

        tag_keys, _ = _zipf_payloads(n_tags, n_tweets * 2, T, 1.4, 3)
        a_ref = plain.arena_for("HashtagGrain")
        a_fus = fused.arena_for("HashtagGrain")
        rows_ref = a_ref.resolve_rows(tag_keys)
        rows_fus = a_fus.resolve_rows(tag_keys)
        for col in ("total", "positive", "negative", "counted",
                    "last_score"):
            np.testing.assert_array_equal(
                np.asarray(a_fus.state[col])[rows_fus],
                np.asarray(a_ref.state[col])[rows_ref],
                err_msg=f"HashtagGrain.{col} diverged under fusion")
        c_ref = plain.arena_for("TweetCounterGrain").read_row(COUNTER_KEY)
        c_fus = fused.arena_for("TweetCounterGrain").read_row(COUNTER_KEY)
        assert int(c_ref["hashtags"]) == int(c_fus["hashtags"])

    run(main())
