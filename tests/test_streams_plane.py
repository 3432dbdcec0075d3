"""Device streams plane (tensor/streams_plane.py): the subscription
arena-CSR, pull-mode scatter-free fan-in, churn under eviction and slot
reuse, overflow park-and-redeliver (the satellite's DeviceFanout
contract included), the batched sqlite dequeue/ack pipeline, fused
threading + live-toggle re-trace, the pub/sub mirror, and metrics
publication.

Marked ``streams`` (pytest.ini); everything runs on the CPU backend.
"""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

import samples.streams as chat  # noqa: F401 — registers the grains
from orleans_tpu.config import TensorEngineConfig
from orleans_tpu.tensor import DeviceSubscriptions, TensorEngine
from orleans_tpu.tensor.vector_grain import seg_max, seg_sum

pytestmark = pytest.mark.streams


def _engine(**cfg):
    cfg.setdefault("auto_fusion_ticks", 0)
    cfg.setdefault("tick_interval", 0.0)
    return TensorEngine(config=TensorEngineConfig(**cfg))


def _fresh_arenas(engine, n_rooms, n_users):
    engine.arena_for("ChatUserGrain").reserve(n_users)
    engine.arena_for("ChatUserGrain").resolve_rows(
        np.arange(n_users, dtype=np.int64))
    engine.arena_for("ChatRoomGrain").reserve(n_rooms)


def _wire(engine, n_rooms=64, n_users=2_000, mean=2.0, seed=0):
    subs = DeviceSubscriptions(engine, "ChatUserGrain", "receive")
    streams, members = chat.build_membership(n_rooms, n_users, mean,
                                             seed=seed)
    subs.subscribe_many(streams, members)
    engine.register_subscriptions("ChatRoomGrain", "publish", subs)
    _fresh_arenas(engine, n_rooms, n_users)
    subs.bind(np.arange(n_rooms, dtype=np.int64))
    return subs


def _user_state(engine, n_users):
    arena = engine.arena_for("ChatUserGrain")
    rows, ok = arena.lookup_rows(np.arange(n_users, dtype=np.int64))
    return {f: np.asarray(arena.state[f])[rows] for f in
            ("received", "last_msg", "checksum")}, ok


# ---------------------------------------------------------------------------
# segment helpers: the pull-mode reductions vs the scatter path
# ---------------------------------------------------------------------------

def test_seg_sum_and_max_segments_match_scatter():
    rng = np.random.default_rng(0)
    n_rows, m = 257, 4_000
    rows_sorted = np.sort(rng.integers(0, n_rows, m)).astype(np.int32)
    seg = np.zeros(n_rows + 1, np.int32)
    seg[1:] = np.cumsum(np.bincount(rows_sorted, minlength=n_rows))
    vals = rng.integers(-50, 50, m).astype(np.int32)
    got_sum = np.asarray(seg_sum(jnp.asarray(vals),
                                 jnp.asarray(rows_sorted), n_rows,
                                 segments=jnp.asarray(seg)))
    want_sum = np.asarray(seg_sum(jnp.asarray(vals),
                                  jnp.asarray(rows_sorted), n_rows))
    np.testing.assert_array_equal(got_sum, want_sum)
    got_max = np.asarray(seg_max(jnp.asarray(vals),
                                 jnp.asarray(rows_sorted), n_rows,
                                 segments=jnp.asarray(seg), fill=-99))
    want = np.full(n_rows, -99, np.int64)
    np.maximum.at(want, rows_sorted, vals)
    # rows with no lanes read fill on the segments path
    empty = seg[1:] == seg[:-1]
    np.testing.assert_array_equal(got_max[~empty], want[~empty])
    assert (got_max[empty] == -99).all()


# ---------------------------------------------------------------------------
# adjacency + expansion
# ---------------------------------------------------------------------------

def test_host_expand_matches_edges_and_batched_mutations():
    subs = DeviceSubscriptions(None, "ChatUserGrain", "receive")
    subs.subscribe_many([1, 1, 2, 5], [10, 11, 20, 50])
    subs.subscribe(2, 21)
    subs.unsubscribe(1, 11)
    assert subs.edge_count == 4
    assert sorted(subs.subscribers_of(2).tolist()) == [20, 21]
    dsts, srcs = subs.host_expand(np.array([2, 1, 7], dtype=np.int64))
    got = sorted(zip(dsts.tolist(), srcs.tolist()))
    assert got == [(10, 1), (20, 0), (21, 0)]
    # add+remove of the same edge within one churn window nets absent
    subs.subscribe(9, 90)
    subs.unsubscribe(9, 90)
    assert len(subs.subscribers_of(9)) == 0


def test_pull_delivery_matches_host_oracle(run):
    async def main():
        engine = _engine()
        subs = _wire(engine, n_rooms=64, n_users=2_000, mean=2.0)
        stats = await chat.run_chat_load(engine, n_rooms=64,
                                         n_users=2_000, n_ticks=5,
                                         subs=subs, verify=True)
        assert stats["oracle"]["received_exact"]
        assert stats["oracle"]["max_exact"]
        assert stats["oracle"]["checksum_exact"]
        # the steady pattern rode the pull fast path, not push
        assert subs.pull_deliveries > 0
        assert subs.push_deliveries == 0

    run(main())


def test_push_delivery_for_unbound_publishes(run):
    """A publish batch that is NOT the bound pattern (subset of
    streams) expands push-mode and still delivers exactly."""

    async def main():
        engine = _engine()
        subs = _wire(engine, n_rooms=32, n_users=500, mean=2.0)
        some = np.array([3, 7, 11], dtype=np.int64)
        msg = np.array([100, 101, 102], dtype=np.int32)
        engine.send_batch("ChatRoomGrain", "publish",
                          jnp.asarray(some.astype(np.int32)),
                          {"msg_id": jnp.asarray(msg)})
        await engine.flush()
        state, ok = _user_state(engine, 500)
        exp = np.zeros(500, np.int64)
        dsts, srcs = subs.host_expand(some)
        np.add.at(exp, dsts, 1)
        np.testing.assert_array_equal(state["received"], exp)
        assert subs.push_deliveries > 0

    run(main())


def test_subscription_churn_rebuilds_and_stays_exact(run):
    async def main():
        engine = _engine()
        subs = _wire(engine, n_rooms=32, n_users=1_000, mean=2.0)
        s1 = await chat.run_chat_load(engine, n_rooms=32, n_users=1_000,
                                      n_ticks=3, subs=subs, verify=True)
        mirror = s1["mirror"]
        v0 = subs.layout_version
        subs.subscribe_many([1, 1, 2], [998, 999, 999])
        drop = subs.subscribers_of(5)
        if len(drop):
            subs.unsubscribe_many(np.full(1, 5), drop[:1])
        s2 = await chat.run_chat_load(engine, n_rooms=32, n_users=1_000,
                                      n_ticks=3, seed=1, subs=subs,
                                      verify=True, mirror=mirror)
        assert subs.layout_version > v0  # churn re-laid the CSR
        for k, v in s2["oracle"].items():
            if k.endswith("_exact"):
                assert v, (k, s2["oracle"])

    run(main())


# ---------------------------------------------------------------------------
# the property the ISSUE names: eviction retires rows before slot reuse
# ---------------------------------------------------------------------------

def test_evicted_subscriber_row_reuse_never_leaks_delivery(run):
    """subscribe → evict subscriber → slot reuse by a DIFFERENT grain →
    publish: the reused row receives nothing; the evicted subscriber's
    deliveries reach its NEW row (push-path reactivation)."""

    async def main():
        engine = _engine()
        subs = _wire(engine, n_rooms=8, n_users=200, mean=2.0)
        await chat.run_chat_load(engine, n_rooms=8, n_users=200,
                                 n_ticks=2, subs=subs)
        arena = engine.arena_for("ChatUserGrain")
        victim = int(subs.subscribers_of(0)[0])
        old_rows, _ = arena.lookup_rows(np.array([victim]))
        old_row = int(old_rows[0])
        arena.evict_keys(np.array([victim]), write_back=False)
        # a different grain reuses the freed slot
        stranger = np.array([9_000], dtype=np.int64)
        arena.resolve_rows(stranger)
        s_rows, ok = arena.lookup_rows(stranger)
        assert ok[0] and int(s_rows[0]) == old_row  # LIFO slot reuse
        before = int(np.asarray(arena.state["received"])[old_row])
        assert before == 0  # scrubbed at free time
        rooms = np.arange(8, dtype=np.int64)
        inj = engine.make_injector("ChatRoomGrain", "publish", rooms)
        inj.inject({"msg_id": np.arange(8, dtype=np.int32) + 500})
        await engine.flush()
        # the reused row never saw the dead subscription's events
        s_rows2, _ = arena.lookup_rows(stranger)
        assert int(np.asarray(arena.state["received"])
                   [int(s_rows2[0])]) == 0
        # the victim reactivated (push path) in a NEW slot and received
        v_rows, v_ok = arena.lookup_rows(np.array([victim]))
        assert v_ok[0]
        want = int(np.sum(subs.edges()[:, 1] == victim))
        assert int(np.asarray(arena.state["received"])
                   [int(v_rows[0])]) == want
        assert subs.retired_edges > 0

    run(main())


def test_eviction_churn_property_randomized(run):
    """Randomized churn property: interleaved subscribe / unsubscribe /
    evict / reuse / publish rounds, oracle equality after every round
    (the 'maintained under the generation/eviction-epoch discipline as
    every other column' claim, property-tested)."""

    async def main():
        from orleans_tpu.tensor import MemoryVectorStore
        from samples.streams import _HostMirror, check_chat_exact
        engine = TensorEngine(
            config=TensorEngineConfig(auto_fusion_ticks=0,
                                      tick_interval=0.0),
            store=MemoryVectorStore())
        n_rooms, n_users = 16, 400
        subs = _wire(engine, n_rooms=n_rooms, n_users=n_users, mean=2.0)
        rooms = np.arange(n_rooms, dtype=np.int64)
        inj = engine.make_injector("ChatRoomGrain", "publish", rooms)
        mirror = _HostMirror(subs, n_users)
        arena = engine.arena_for("ChatUserGrain")
        rng = np.random.default_rng(42)
        for rnd in range(8):
            op = rnd % 4
            if op == 1:
                subs.subscribe_many(
                    rng.integers(0, n_rooms, 5),
                    rng.integers(0, n_users, 5))
            elif op == 2:
                e = subs.edges()
                if len(e):
                    pick = e[rng.integers(0, len(e), 3)]
                    subs.unsubscribe_many(pick[:, 0], pick[:, 1])
            elif op == 3:
                victims = rng.choice(n_users, 20, replace=False) \
                    .astype(np.int64)
                arena.evict_keys(victims, write_back=True)
                mirror.evict_keys(victims)
                # slot reuse by fresh, unsubscribed grains
                arena.resolve_rows(
                    np.arange(10, dtype=np.int64) + 10_000 + rnd * 100)
            msg = (rng.integers(0, 10_000, n_rooms)).astype(np.int32)
            inj.inject({"msg_id": msg})
            await engine.flush()
            mirror.publish(rooms, msg.astype(np.int64))
            oracle = check_chat_exact(engine, n_users, mirror)
            assert oracle["received_exact"] and oracle["max_exact"] \
                and oracle["checksum_exact"], (rnd, oracle)

    run(main())


# ---------------------------------------------------------------------------
# overflow park-and-redeliver (the DeviceFanout satellite contract)
# ---------------------------------------------------------------------------

def test_subscription_overflow_parks_and_redelivers_with_stamp(run):
    """Push expansion past the CSR width parks the source lanes and
    re-expands them at a quiescence point; the latency ledger records
    the redelivered lanes at their ORIGINAL stamp (nonzero delta)."""

    async def main():
        engine = _engine()
        subs = DeviceSubscriptions(engine, "ChatUserGrain", "receive")
        # 300 edges on one stream → width 512; publishing the stream
        # twice in one batch needs 600 slots → the second lane parks
        subs.subscribe_many(np.zeros(300, np.int64),
                            np.arange(300, dtype=np.int64))
        engine.register_subscriptions("ChatRoomGrain", "publish", subs)
        _fresh_arenas(engine, 4, 300)
        dup = jnp.asarray(np.zeros(2, np.int32))
        engine.send_batch("ChatRoomGrain", "publish", dup,
                          {"msg_id": jnp.asarray(
                              np.array([7, 8], np.int32))})
        await engine.flush()
        state, ok = _user_state_300(engine)
        # both publishes delivered to every subscriber — nothing lost
        np.testing.assert_array_equal(state, 2)
        assert subs.dropped_lanes >= 1
        assert subs.redeliveries >= 1
        # the ledger saw the redelivered lanes at a NONZERO tick delta
        counts = engine.ledger.fetch_counts()
        slot = engine.ledger.slot_for("ChatUserGrain", "receive")
        assert counts[slot, 1:].sum() > 0, counts[slot]

    def _user_state_300(engine):
        arena = engine.arena_for("ChatUserGrain")
        rows, ok = arena.lookup_rows(np.arange(300, dtype=np.int64))
        return np.asarray(arena.state["received"])[rows], ok

    run(main())


def test_fanout_overflow_redelivers_through_engine(run):
    """The DeviceFanout regression: an over-width publish round through
    a registered fan-out no longer raises FanoutOverflowError — the
    parked lanes re-deliver and the delivery multiset is complete."""
    from orleans_tpu.tensor import DeviceFanout
    from samples.chirper import ChirperAccount  # noqa: F401

    async def main():
        engine = _engine()
        fan = DeviceFanout(budget=1 << 20)
        for d in range(300):
            fan.follow(1, 100 + d)
        engine.register_fanout("ChirperAccount", "publish", fan,
                               "ChirperAccount", "new_chirp")
        engine.arena_for("ChirperAccount").reserve(512)
        engine.arena_for("ChirperAccount").resolve_rows(
            np.concatenate([[1], np.arange(100, 400)]).astype(np.int64))
        # width is 512 (300 edges → 256-aligned); 2 publishes of key 1
        # need 600 slots — the old code raised at flush
        engine.send_batch(
            "ChirperAccount", "publish",
            jnp.asarray(np.array([1, 1], np.int32)),
            {"chirp_id": jnp.asarray(np.array([5, 6], np.int32))})
        await engine.flush()  # no FanoutOverflowError
        arena = engine.arena_for("ChirperAccount")
        rows, ok = arena.lookup_rows(
            np.arange(100, 400, dtype=np.int64))
        received = np.asarray(arena.state["received"])[rows]
        np.testing.assert_array_equal(received, 2)
        assert fan.dropped_lanes >= 1

    run(main())


# ---------------------------------------------------------------------------
# fused threading + live toggle
# ---------------------------------------------------------------------------

def test_fused_chat_exact_and_route_version_retrace(run):
    async def main():
        engine = TensorEngine()
        subs = _wire(engine, n_rooms=32, n_users=800, mean=2.0)
        rooms = np.arange(32, dtype=np.int64)
        prog = engine.fuse_ticks("ChatRoomGrain", "publish", rooms)
        T = 4

        def stacked(base):
            return {"msg_id": np.arange(T * 32, dtype=np.int32)
                    .reshape(T, 32) + base}

        prog.run(stacked(0))
        assert prog.verify() == 0
        compiled0 = prog._compiled
        # adjacency mutation bumps layout_version → prepare re-traces
        # with cause config_toggle.  Pick a user NOT yet in room 0 so
        # the host oracle below is unambiguous.
        newbie = int(np.setdiff1d(np.arange(800),
                                  subs.subscribers_of(0))[0])
        subs.subscribe(0, newbie)
        before = engine.compile_tracker.snapshot()["by_cause"] \
            .get("config_toggle", 0)
        prog.run(stacked(1000))
        assert prog.verify() == 0
        assert prog._compiled is not compiled0
        after = engine.compile_tracker.snapshot()["by_cause"] \
            .get("config_toggle", 0)
        assert after == before + 1
        # the fused deliveries match the host replay: every edge saw
        # 2T publishes except the new one, which saw only the second T
        state, ok = _user_state(engine, 800)
        exp = np.zeros(800, np.int64)
        dsts, _srcs = subs.host_expand(rooms)
        np.add.at(exp, dsts, 2 * T)
        exp[newbie] -= T  # the new edge missed the first window
        np.testing.assert_array_equal(state["received"], exp)

    run(main())


def test_live_toggle_host_path_delivers_and_retraces(run):
    async def main():
        engine = _engine()
        subs = _wire(engine, n_rooms=16, n_users=300, mean=2.0)
        stats = await chat.run_chat_load(engine, n_rooms=16,
                                         n_users=300, n_ticks=2,
                                         subs=subs, verify=True)
        mirror = stats["mirror"]
        engine.config.stream_plane = False  # live toggle → host path
        s2 = await chat.run_chat_load(engine, n_rooms=16, n_users=300,
                                      n_ticks=2, seed=3, subs=subs,
                                      verify=True, mirror=mirror)
        for k, v in s2["oracle"].items():
            if k.endswith("_exact"):
                assert v, (k, s2["oracle"])
        engine.config.stream_plane = True

    run(main())


def test_plane_disabled_fused_window_never_verifies(run):
    """Review regression: with a route registered and the plane
    live-DISABLED, a fused window cannot run the host-expansion path —
    it must count every routed source lane as a miss (verify() fails,
    the unfused replay delivers) instead of verifying clean while
    silently dropping every subscriber delivery."""

    async def main():
        engine = TensorEngine()
        subs = _wire(engine, n_rooms=16, n_users=300, mean=2.0)
        engine.config.stream_plane = False
        rooms = np.arange(16, dtype=np.int64)
        prog = engine.fuse_ticks("ChatRoomGrain", "publish", rooms)
        prog.run({"msg_id": np.arange(4 * 16, dtype=np.int32)
                  .reshape(4, 16)})
        assert prog.verify() > 0  # the window is NOT exact by design
        engine.config.stream_plane = True

    run(main())


def test_wide_stream_key_degrades_to_host_expansion(run):
    """Review regression: a publish carrying a stream key outside the
    int31 device domain must not error mid-tick — it expands on host
    (no subscribers can exist for it in the int31-keyed CSR, so it
    delivers nothing) and the rest of the round flows."""

    async def main():
        engine = _engine()
        subs = _wire(engine, n_rooms=8, n_users=100, mean=2.0)
        wide = np.array([2**40 + 5], dtype=np.int64)
        engine.send_batch("ChatRoomGrain", "publish", wide,
                          {"msg_id": np.array([1], np.int32)})
        await engine.flush()  # no OverflowError
        arena = engine.arena_for("ChatRoomGrain")
        _r, ok = arena.lookup_rows(wide)
        assert ok[0]  # the ingress apply itself landed

    run(main())


def test_rollback_replays_under_mutation_settled_adjacency(run):
    """A subscribe() while an auto-fused chain is unverified settles
    the chain FIRST — the 'rollback restores adjacency state' contract
    held structurally: buffered ticks always replay under the adjacency
    they were consumed with."""

    async def main():
        engine = TensorEngine(config=TensorEngineConfig(
            auto_fusion_ticks=2, auto_fusion_window=2,
            auto_fusion_verify_windows=16, tick_interval=0.0))
        subs = _wire(engine, n_rooms=8, n_users=100, mean=1.0)
        rooms = np.arange(8, dtype=np.int64)
        inj = engine.make_injector("ChatRoomGrain", "publish", rooms)
        for t in range(10):
            inj.inject({"msg_id": np.arange(8, dtype=np.int32) + 8 * t})
            await engine.drain_queues()
        assert engine.autofuser._unverified  # a chain is open
        # the new subscriber is a fresh key outside the population, so
        # the oracle below is unambiguous (it must receive NOTHING —
        # all 10 publishes pre-date the edge)
        subs.subscribe(0, 50_000)
        assert not engine.autofuser._unverified  # settled first
        await engine.flush()
        state, ok = _user_state(engine, 100)
        exp = np.zeros(100, np.int64)
        dsts, _ = subs.host_expand(rooms)
        keep = dsts < 100  # drop the post-hoc edge from the replay
        np.add.at(exp, dsts[keep], 10)
        np.testing.assert_array_equal(state["received"], exp)
        # the chain-consumed ticks replayed under the OLD adjacency:
        # the late subscriber can only have seen the (at most one)
        # tick still buffered at mutation time — never the windowed 8+
        arena = engine.arena_for("ChatUserGrain")
        r, ok2 = arena.lookup_rows(np.array([50_000], dtype=np.int64))
        late = int(np.asarray(arena.state["received"])[int(r[0])]) \
            if ok2[0] else 0
        assert late <= 2, late

    run(main())


# ---------------------------------------------------------------------------
# the batched sqlite dequeue/ack pipeline (satellite)
# ---------------------------------------------------------------------------

def test_sqlite_pull_cycle_is_one_transaction(run, tmp_path):
    """Before/after contract: k produced items land in ONE enqueue
    transaction per produce(), and a pull cycle's dequeue+ack is ONE
    transaction (the legacy path paid one enqueue per item and one ack
    per delivered run)."""
    from orleans_tpu.plugins.sqlite_queue import SqliteQueueAdapter
    from orleans_tpu.streams.core import StreamId
    from orleans_tpu.streams.persistent import QueueMessage

    async def main():
        adapter = SqliteQueueAdapter(path=str(tmp_path / "q.db"),
                                     n_queues=2)
        sid = StreamId("p", "ns", 1)
        t0 = adapter.transactions
        await adapter.queue_messages(
            0, [QueueMessage(stream_id=sid, item=i, seq=-1)
                for i in range(16)])
        assert adapter.transactions - t0 == 1  # 16 items, ONE txn
        recv = adapter.create_receiver(0)
        t1 = adapter.transactions
        msgs = await recv.pull_and_ack(8, -1)
        assert [m.item for m in msgs] == list(range(8))
        assert adapter.transactions - t1 == 1  # dequeue, no ack yet
        t2 = adapter.transactions
        msgs2 = await recv.pull_and_ack(8, msgs[-1].seq)
        assert adapter.transactions - t2 == 1  # ack + dequeue, ONE txn
        assert [m.item for m in msgs2] == list(range(8, 16))
        # the ack landed durably: a fresh receiver starts past it
        msgs3 = await recv.pull_and_ack(16, msgs2[-1].seq)
        assert msgs3 == []
        adapter.close()

    run(main())


def test_pulling_agent_batches_acks_per_cycle(run, tmp_path):
    """End to end through a pulling agent: delivering N events costs
    O(cycles) adapter transactions, not O(events) — the before/after
    count the satellite asks for."""
    from orleans_tpu.plugins.sqlite_queue import SqliteQueueAdapter
    from orleans_tpu.streams import PersistentStreamProvider
    from orleans_tpu.testing.cluster import TestingCluster
    from samples.streams import run_chat_stream_load

    async def main():
        adapter = SqliteQueueAdapter(path=str(tmp_path / "q2.db"),
                                     n_queues=1)

        def setup(silo):
            p = PersistentStreamProvider(adapter, pull_period=0.001,
                                         batch_size=16)
            p.bind_tensor_sink("chat-pub", "ChatRoomGrain", "publish")
            silo.add_stream_provider("cstream", p)

        cluster = await TestingCluster(n_silos=1,
                                       silo_setup=setup).start()
        try:
            t0 = adapter.transactions
            stats = await run_chat_stream_load(
                cluster.silos[0], n_rooms=64, n_users=1_000,
                mean_memberships=2.0, n_slabs=8)
            txns = adapter.transactions - t0
            # 8 produce txns + O(pull cycles) combined dequeue/ack
            # round-trips — orders of magnitude below the per-event
            # floor (one adapter round-trip per delivered queue event
            # would be >= 512 here)
            assert txns < 60, txns
            assert stats["messages"] > 0
        finally:
            await cluster.stop()
        adapter.close()

    run(main())


def test_pubsub_mirror_feeds_device_plane(run):
    """Explicit pub/sub subscriptions through a provider with a bound
    device plane mirror into the adjacency (and out again)."""
    from orleans_tpu.streams.core import StreamId, device_stream_key
    from orleans_tpu.streams.pubsub import PubSubStreamProviderMixin

    class FakeHandle:
        def __init__(self, sid, key):
            self.stream_id = sid
            self.subscription_id = key
            self.consumer = type("G", (), {"primary_key_int": key})()

    class FakeProvider(PubSubStreamProviderMixin):
        name = "fake"

        def _pubsub(self, stream_id):
            class _P:
                async def register_consumer(self, h): ...
                async def unregister_consumer(self, h): ...
            return _P()

    async def main():
        subs = DeviceSubscriptions(None, "ChatUserGrain", "receive")
        p = FakeProvider()
        p.bind_device_subscriptions("rooms", subs)
        sid = StreamId("fake", "rooms", 7)
        await p.register_subscription(FakeHandle(sid, 42))
        assert subs.subscribers_of(device_stream_key(sid)).tolist() \
            == [42]
        await p.unsubscribe(FakeHandle(sid, 42))
        assert len(subs.subscribers_of(device_stream_key(sid))) == 0
        # other namespaces don't mirror
        await p.register_subscription(
            FakeHandle(StreamId("fake", "other", 7), 43))
        assert subs.edge_count == 0

    run(main())


# ---------------------------------------------------------------------------
# grouped twitter (the pull-mode firehose)
# ---------------------------------------------------------------------------

def test_twitter_grouped_bit_exact_vs_ungrouped(run):
    from samples.twitter_sentiment import (_zipf_payloads,
                                           run_twitter_load,
                                           run_twitter_load_grouped)

    async def main():
        e1 = TensorEngine()
        await run_twitter_load_grouped(e1, n_tweets_per_tick=2_000,
                                       n_hashtags=500, n_ticks=4,
                                       window=4)
        e2 = _engine()
        await run_twitter_load(e2, n_tweets_per_tick=2_000,
                               n_hashtags=500, n_ticks=4)
        tag_keys, _ = _zipf_payloads(500, 1, 1, 1.4, 0)
        a1, a2 = (e.arena_for("HashtagGrain") for e in (e1, e2))
        r1, ok1 = a1.lookup_rows(tag_keys)
        r2, ok2 = a2.lookup_rows(tag_keys)
        assert ok1.all()
        sel = ok2
        for f in ("total", "positive", "negative", "counted",
                  "last_score"):
            x1 = np.asarray(a1.state[f])[r1]
            x2 = np.asarray(a2.state[f])[r2]
            np.testing.assert_array_equal(x1[sel], x2[sel], err_msg=f)
            assert not np.any(x1[~sel]), f  # untouched keys stay init
        c1 = int(np.asarray(
            e1.arena_for("TweetCounterGrain").state["hashtags"])[0])
        c2 = int(np.asarray(
            e2.arena_for("TweetCounterGrain").state["hashtags"])[0])
        assert c1 == c2

    run(main())


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_stream_metrics_declared_and_collected(run):
    from orleans_tpu.metrics import CATALOG
    for name in ("stream.published_events", "stream.delivered_events",
                 "stream.subscriptions", "stream.cold_subscribers",
                 "stream.rebuilds", "stream.retired_edges",
                 "stream.dropped_lanes", "stream.redeliveries"):
        assert name in CATALOG, name

    from orleans_tpu.runtime.silo import Silo
    from orleans_tpu.config import SiloConfig

    async def main():
        silo = Silo(config=SiloConfig(name="smetrics"))
        await silo.start()
        try:
            engine = silo.tensor_engine
            subs = DeviceSubscriptions(engine, "ChatUserGrain",
                                       "receive")
            subs.subscribe_many([1, 2], [10, 20])
            engine.register_subscriptions("ChatRoomGrain", "publish",
                                          subs)
            _fresh_arenas(engine, 4, 30)
            engine.send_batch("ChatRoomGrain", "publish",
                              np.array([1, 2], dtype=np.int64),
                              {"msg_id": np.array([5, 6], np.int32)})
            await engine.flush()
            snap = silo.collect_metrics()  # strict: undeclared raises
            assert "stream.published_events" in snap["counters"]
            assert "stream.delivered_events" in snap["counters"]
            assert "stream.subscriptions" in snap["gauges"]
        finally:
            await silo.stop()

    run(main())

