"""Workload attribution plane (tensor/attribution.py): device hot-grain
counts + count-min sketch vs host oracles, eviction/rollback
bit-exactness, the delta-plan hot path, HotSet/skew/SLO publication
through silo → load publisher → dashboard.

Marked ``attribution`` (pytest.ini); everything runs on the CPU backend.
"""

import asyncio
import json

import numpy as np
import pytest

import samples.presence  # noqa: F401 — registers the vector grains
from orleans_tpu.config import MetricsConfig, TensorEngineConfig
from orleans_tpu.tensor import TensorEngine
from orleans_tpu.tensor import attribution as attr_mod

pytestmark = pytest.mark.attribution


def _engine(**cfg):
    cfg.setdefault("auto_fusion_ticks", 0)
    cfg.setdefault("tick_interval", 0.0)
    return TensorEngine(config=TensorEngineConfig(**cfg))


def _drive_presence(engine, keys, n_games, ticks, start_tick=0):
    """One send_batch heartbeat per tick; returns the per-key oracle."""
    n = int(keys.max()) + 1
    oracle = np.zeros(n, np.int64)
    for t in range(ticks):
        oracle += np.bincount(keys, minlength=n)
        engine.send_batch(
            "PresenceGrain", "heartbeat", keys,
            {"game": (keys % n_games).astype(np.int32),
             "score": np.ones(len(keys), np.float32),
             "tick": np.full(len(keys), start_tick + t + 1, np.int32)})
        asyncio.get_event_loop()  # no-op; drained by caller
    return oracle


# ---------------------------------------------------------------------------
# fold exactness + sketch bounds
# ---------------------------------------------------------------------------

def test_fold_matches_numpy_replay():
    """Unit-level: one fold's counts/sketch/slots vs a numpy replay,
    masked and out-of-range lanes excluded everywhere."""
    import jax
    import jax.numpy as jnp

    eng = _engine()
    att = eng.attribution
    arena = eng.arena_for("PresenceGrain")
    arena.resolve_rows(np.arange(64, dtype=np.int64))
    rows = np.asarray([0, 1, 1, 5, 63, -1, 99999, 2], np.int32)
    mask = np.asarray([1, 1, 1, 1, 1, 1, 1, 0], bool)
    att.record_group(arena, "PresenceGrain", "heartbeat",
                     jnp.asarray(rows), jnp.asarray(mask))
    att.flush_folds()  # reading the raw arrays below, not a snapshot
    valid = mask & (rows >= 0) & (rows < arena.capacity)
    expect = np.bincount(rows[valid], minlength=arena.capacity)
    got = np.asarray(jax.device_get(att.counts_for("PresenceGrain")))
    np.testing.assert_array_equal(got, expect)
    cms = np.asarray(jax.device_get(att.cms_for("PresenceGrain")))
    # every sketch depth holds exactly the valid-lane total
    np.testing.assert_array_equal(cms.sum(axis=1),
                                  np.full(att.cms_depth, valid.sum()))
    slot = att.slots.slot_for("PresenceGrain", "heartbeat")
    slots = np.asarray(jax.device_get(att._slot_arr()))
    assert slots[slot] == valid.sum()


def test_flush_defers_under_trace_and_folds_after():
    """A flush reached inside an active jit trace stores no tracer: the
    fold stays buffered and lands on the next concrete flush."""
    import jax
    import jax.numpy as jnp

    eng = _engine()
    att = eng.attribution
    arena = eng.arena_for("PresenceGrain")
    arena.resolve_rows(np.arange(8, dtype=np.int64))
    rows = np.asarray([0, 3, 3, 7], np.int32)
    att.record_group(arena, "PresenceGrain", "heartbeat",
                     jnp.asarray(rows), jnp.ones(4, bool))

    def traced(x):
        att.flush_folds()
        return x + 1

    assert int(jax.jit(traced)(1)) == 2
    assert len(att._pending) == 1
    counts = att.counts_for("PresenceGrain")
    assert not isinstance(counts, jax.core.Tracer)
    assert int(np.asarray(counts).sum()) == 0
    att.flush_folds()
    assert att._pending == []
    np.testing.assert_array_equal(
        np.asarray(att.counts_for("PresenceGrain")),
        np.bincount(rows, minlength=arena.capacity))


def test_topk_matches_host_oracle_on_zipf():
    """The tentpole contract at test scale: device HotSet == host
    bincount oracle on a skewed workload (the bench tier re-asserts at
    1M grains)."""
    async def go():
        eng = _engine()
        n, n_games = 20_000, 50
        rng = np.random.default_rng(7)
        eng.arena_for("PresenceGrain").resolve_rows(
            np.arange(n, dtype=np.int64))
        eng.arena_for("GameGrain").resolve_rows(
            np.arange(n_games, dtype=np.int64))
        # bounded Zipf-ish skew: rank-weighted sample with repeats
        p = 1.0 / np.arange(1, n + 1) ** 1.1
        cdf = np.cumsum(p / p.sum())
        keys = np.minimum(np.searchsorted(cdf, rng.random(30_000)),
                          n - 1).astype(np.int64)
        oracle = np.zeros(n, np.int64)
        for t in range(3):
            oracle += np.bincount(keys, minlength=n)
            eng.send_batch(
                "PresenceGrain", "heartbeat", keys,
                {"game": (keys % n_games).astype(np.int32),
                 "score": np.ones(len(keys), np.float32),
                 "tick": np.full(len(keys), t + 1, np.int32)})
            await eng.drain_queues()
        await eng.flush()
        snap = eng.attribution.snapshot()
        a = snap["arenas"]["PresenceGrain"]
        assert a["hot"], "no hot grains published"
        for h in a["hot"]:
            assert oracle[h["key"]] == h["msgs"]
            # the sketch's one-sided error bound on the candidates
            assert h["sketch_est"] >= h["msgs"]
            assert 0 < h["confidence"] <= 1.0
        k = len(a["hot"])
        assert [h["msgs"] for h in a["hot"]] \
            == np.sort(oracle)[-k:][::-1].tolist()
        assert a["total_msgs"] == oracle.sum()
        sk = a["skew"]
        assert sk["gini"] > 0.3 and sk["p99_to_mean"] > 1.0
        assert sk["hot_rows"] == int((oracle > 0).sum())

    asyncio.run(go())


def test_sketch_never_undercounts_under_collisions():
    """A tiny sketch (forced collisions) must still never undercount —
    the count-min property the HotSet's confidence prices."""
    import jax
    import jax.numpy as jnp

    eng = _engine()
    eng.metrics_config.attribution_cms_width = 16
    att = eng.attribution
    att.configure(cms_width=16, cms_depth=2)
    arena = eng.arena_for("PresenceGrain")
    arena.resolve_rows(np.arange(256, dtype=np.int64))
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 256, 2_000).astype(np.int32)
    att.record_group(arena, "PresenceGrain", "heartbeat",
                     jnp.asarray(rows), jnp.ones(2_000, bool))
    att.flush_folds()
    true = np.bincount(rows, minlength=256)
    cms = np.asarray(jax.device_get(att.cms_for("PresenceGrain")))
    seeds = np.asarray(attr_mod.CMS_SEEDS[:2], np.uint32)
    h = np.asarray(jax.device_get(attr_mod.cms_hash(
        jnp.asarray(np.arange(256, dtype=np.int32)),
        jnp.asarray(seeds), 16)))
    est = np.min(cms[np.arange(2)[:, None], h], axis=0)
    assert (est >= true).all(), "count-min sketch undercounted"


# ---------------------------------------------------------------------------
# row lifecycle: eviction epochs, growth remap
# ---------------------------------------------------------------------------

def test_eviction_retires_counts_bit_exactly():
    """Evicted grains' counts retire per key; a reused row never
    inherits them; totals survive the epoch bit-exactly (live+retired
    vs the host replay)."""
    async def go():
        eng = _engine()
        n, n_games = 512, 8
        keys = np.arange(n, dtype=np.int64)
        arena = eng.arena_for("PresenceGrain")
        arena.resolve_rows(keys)
        eng.arena_for("GameGrain").resolve_rows(
            np.arange(n_games, dtype=np.int64))
        replay: dict = {}

        async def traffic(ks, ticks, t0):
            for t in range(ticks):
                for k in ks.tolist():
                    replay[k] = replay.get(k, 0) + 1
                eng.send_batch(
                    "PresenceGrain", "heartbeat", ks,
                    {"game": (ks % n_games).astype(np.int32),
                     "score": np.ones(len(ks), np.float32),
                     "tick": np.full(len(ks), t0 + t, np.int32)})
                await eng.drain_queues()
            await eng.flush()

        await traffic(keys, 3, 1)
        epoch0 = arena.eviction_epoch
        # evict the first half (write_back=False keeps the store out)
        rows, found = arena.lookup_rows(keys[:n // 2])
        assert found.all()
        arena.deactivate_idle_rows(rows, 10**9, write_back=False)
        assert arena.eviction_epoch > epoch0
        assert eng.attribution.stats()["retired_rows"] >= n // 2
        # traffic to the surviving half + NEW keys that reuse freed rows
        fresh = np.arange(n, n + n // 4, dtype=np.int64)
        await traffic(np.concatenate([keys[n // 2:], fresh]), 2, 10)
        totals = eng.attribution.per_key_totals("PresenceGrain")
        assert totals == replay, "per-key totals diverged across epoch"
        # a fresh key reusing an evicted slot carries ONLY its own count
        for k in fresh.tolist():
            assert totals[k] == 2

    asyncio.run(go())


def test_growth_remap_preserves_totals():
    """Arena growth moves rows; the counts column remaps on device and
    keys keep their totals."""
    async def go():
        eng = _engine()
        n_games = 4
        keys = np.arange(100, dtype=np.int64)
        eng.arena_for("GameGrain").resolve_rows(
            np.arange(n_games, dtype=np.int64))
        arena = eng.arena_for("PresenceGrain")
        arena.resolve_rows(keys)
        cap0 = arena.capacity
        replay: dict = {}

        async def traffic(ks, tick):
            for k in ks.tolist():
                replay[k] = replay.get(k, 0) + 1
            eng.send_batch(
                "PresenceGrain", "heartbeat", ks,
                {"game": (ks % n_games).astype(np.int32),
                 "score": np.ones(len(ks), np.float32),
                 "tick": np.full(len(ks), tick, np.int32)})
            await eng.drain_queues()
            await eng.flush()

        await traffic(keys, 1)
        # out-of-band grow: capacity quadruples, rows MOVE (generation
        # bump) — the counts column must remap with them
        arena.reserve(cap0 * 4)
        assert arena.capacity > cap0, "reserve did not grow"
        await traffic(keys, 2)
        totals = eng.attribution.per_key_totals("PresenceGrain")
        assert totals == replay

    asyncio.run(go())


def test_compaction_remap_flushes_pending_folds():
    """A fold still BUFFERED when a row move lands must flush before
    the remap: applied after, its deltas would scatter at the old row
    indices — rows the surviving grains no longer occupy (single-shard
    growth happens to keep indices stable, compaction does not)."""
    import jax.numpy as jnp

    eng = _engine()
    att = eng.attribution
    arena = eng.arena_for("PresenceGrain")
    keys = np.arange(10, dtype=np.int64)
    arena.resolve_rows(keys)
    # free the low rows so compaction MOVES the survivors down
    r_low, found = arena.lookup_rows(keys[:5])
    assert found.all()
    arena.deactivate_idle_rows(r_low, 10**9, write_back=False)
    # one fold for the survivors, buffered (below _FLUSH_CAP)
    r_hi, found = arena.lookup_rows(keys[5:])
    assert found.all()
    att.record_group(arena, "PresenceGrain", "heartbeat",
                     jnp.asarray(r_hi, jnp.int32),
                     jnp.ones(len(r_hi), bool))
    assert att.stats()["pending_folds"] == 1
    arena._compact()
    assert (arena.lookup_rows(keys[5:])[0] != r_hi).any(), \
        "compaction did not move the surviving rows"
    totals = att.per_key_totals("PresenceGrain")
    assert totals == {int(k): 1 for k in keys[5:]}, totals


# ---------------------------------------------------------------------------
# fused windows: accumulation, rollback restore, live toggle
# ---------------------------------------------------------------------------

def test_fused_window_counts_match():
    """A fused window's in-scan folds land the same totals the unfused
    engine records."""
    async def go():
        import jax.numpy as jnp
        eng = TensorEngine()
        players = np.arange(128, dtype=np.int64)
        eng.arena_for("PresenceGrain").resolve_rows(players)
        eng.arena_for("GameGrain").resolve_rows(
            np.arange(4, dtype=np.int64))
        prog = eng.fuse_ticks("PresenceGrain", "heartbeat", players)
        static = {"game": jnp.zeros(128, jnp.int32),
                  "score": jnp.ones(128, jnp.float32)}
        prog.run({"tick": jnp.arange(1, 4, dtype=jnp.int32)},
                 static_args=static)
        assert prog.verify() == 0
        snap = eng.attribution.snapshot()
        assert snap["arenas"]["PresenceGrain"]["total_msgs"] == 128 * 3
        assert snap["arenas"]["GameGrain"]["total_msgs"] == 128 * 3
        assert snap["methods"]["PresenceGrain.heartbeat"] == 128 * 3

    asyncio.run(go())


@pytest.fixture(scope="module")
def attr_hop_grains():
    """A steerable two-hop pair to force fused-window rollbacks (the
    test_metrics recipe, distinct type names)."""
    import jax.numpy as jnp
    from orleans_tpu.core.grain import batched_method
    from orleans_tpu.tensor import (
        Batch,
        Emit,
        VectorGrain,
        field,
        vector_grain,
    )
    from orleans_tpu.tensor.vector_grain import (
        scatter_add_rows,
        vector_type,
    )

    if vector_type("AttrTestHopGrain") is not None:
        return

    @vector_grain
    class AttrTestLwwGrain(VectorGrain):
        count = field(jnp.int32, 0)

        @batched_method
        @staticmethod
        def put(state, batch: Batch, n_rows: int):
            ones = jnp.ones_like(batch.rows, jnp.int32) * batch.mask
            return {**state, "count": scatter_add_rows(
                state["count"], batch.rows, ones)}

    @vector_grain
    class AttrTestHopGrain(VectorGrain):
        sent = field(jnp.int32, 0)

        @batched_method
        @staticmethod
        def send(state, batch: Batch, n_rows: int):
            ones = jnp.ones_like(batch.rows, jnp.int32) * batch.mask
            state = {**state, "sent": scatter_add_rows(
                state["sent"], batch.rows, ones)}
            emit = Emit(interface="AttrTestLwwGrain", method="put",
                        keys=batch.args["dst"],
                        args={"v": batch.args["v"]}, mask=batch.mask)
            return state, None, (emit,)


def test_rollback_restores_attribution(attr_hop_grains):
    """A rolled-back fused window's in-scan attribution must unwind —
    the unfused replay re-records every message exactly once."""
    async def go():
        n, T = 16, 24
        src = np.arange(n, dtype=np.int64)
        eng = TensorEngine(config=TensorEngineConfig(
            auto_fusion_ticks=3, auto_fusion_window=4, tick_interval=0.0,
            auto_fusion_max_rollbacks=100))
        eng.arena_for("AttrTestHopGrain").reserve(n)
        eng.arena_for("AttrTestLwwGrain").reserve(n + 64)
        inj = eng.make_injector("AttrTestHopGrain", "send", src)
        cold_tick = 18
        for t in range(T):
            dst = np.full(n, 5000 if t == cold_tick else 0, np.int32)
            inj.inject({"dst": dst, "v": np.full(n, t + 1, np.int32)})
            await eng.drain_queues()
        await eng.flush()
        assert eng.autofuser.windows_rolled_back >= 1, \
            "cold destination did not trigger a rollback"
        hop = eng.attribution.per_key_totals("AttrTestHopGrain")
        lww = eng.attribution.per_key_totals("AttrTestLwwGrain")
        assert hop == {k: T for k in range(n)}
        assert lww == {0: n * (T - 1), 5000: n}
        snap = eng.attribution.snapshot()
        assert snap["methods"]["AttrTestHopGrain.send"] == n * T
        assert snap["methods"]["AttrTestLwwGrain.put"] == n * T

    asyncio.run(go())


def test_toggle_retraces_fused_program():
    """A live attribution toggle takes effect on a steady fused program
    (prepare() re-traces on the build-signature change), and counts
    hold across the disabled span."""
    async def go():
        import jax.numpy as jnp
        eng = TensorEngine()
        players = np.arange(128, dtype=np.int64)
        eng.arena_for("PresenceGrain").resolve_rows(players)
        eng.arena_for("GameGrain").resolve_rows(
            np.arange(4, dtype=np.int64))
        prog = eng.fuse_ticks("PresenceGrain", "heartbeat", players)
        static = {"game": jnp.zeros(128, jnp.int32),
                  "score": jnp.ones(128, jnp.float32)}

        def window(t0):
            prog.run({"tick": jnp.arange(t0, t0 + 2, dtype=jnp.int32)},
                     static_args=static)
            assert prog.verify() == 0

        def total():
            snap = eng.attribution.snapshot(cache=False)
            a = snap["arenas"].get("PresenceGrain")
            return a["total_msgs"] if a else 0

        window(1)
        assert total() == 256
        eng.attribution.configure(enabled=False)
        window(3)
        assert total() == 256
        eng.attribution.configure(enabled=True)
        window(5)
        assert total() == 512

    asyncio.run(go())


# ---------------------------------------------------------------------------
# hot path: delta plans, snapshot cache, transfer budget
# ---------------------------------------------------------------------------

def test_plan_memo_and_snapshot_budget():
    """Steady injector state: the delta-plan memo serves every fold
    (host-proven or device-checked, no per-tick plan builds), snapshots
    cost ONE d2h each and cache until new folds arrive."""
    async def go():
        import jax.numpy as jnp
        eng = _engine()
        n, n_games = 2_000, 8
        keys = np.arange(n, dtype=np.int64)
        eng.arena_for("PresenceGrain").resolve_rows(keys)
        eng.arena_for("GameGrain").resolve_rows(
            np.arange(n_games, dtype=np.int64))
        inj = eng.make_injector("PresenceGrain", "heartbeat", keys)
        payload = {"game": jnp.asarray((keys % n_games).astype(np.int32)),
                   "score": jnp.asarray(np.ones(n, np.float32))}
        for t in range(10):
            inj.inject({**payload, "tick": np.int32(t + 1)})
            await eng.drain_queues()
        await eng.flush()
        st = eng.attribution.stats()
        assert st["plan_builds"] <= 4, st  # one per group, not per tick
        assert st["plan_hits"] + st["plan_checked"] >= 16, st
        assert st["stale_folds"] == 0
        f0 = eng.attribution.d2h_fetches
        eng.attribution.snapshot()
        assert eng.attribution.d2h_fetches == f0 + 1
        eng.attribution.snapshot()  # cached: no new folds since
        assert eng.attribution.d2h_fetches == f0 + 1
        inj.inject({**payload, "tick": np.int32(99)})
        await eng.drain_queues()
        await eng.flush()
        eng.attribution.snapshot()
        assert eng.attribution.d2h_fetches == f0 + 2

    asyncio.run(go())


def test_checked_plan_stays_exact_on_changing_content():
    """Same-shaped batches with CHANGING destination content: the
    checked kernel's device compare rejects the stale plan, the scatter
    fallback keeps counts exact, and the stale counter surfaces at the
    next snapshot."""
    import jax.numpy as jnp

    eng = _engine()
    att = eng.attribution
    arena = eng.arena_for("PresenceGrain")
    arena.resolve_rows(np.arange(64, dtype=np.int64))
    rng = np.random.default_rng(11)
    expect = np.zeros(arena.capacity, np.int64)
    mask = jnp.ones(32, bool)
    for _ in range(5):
        rows = rng.integers(0, 64, 32).astype(np.int32)
        expect += np.bincount(rows, minlength=arena.capacity)
        # fresh device arrays each call — jit-output-like identity churn
        att.record_group(arena, "PresenceGrain", "heartbeat",
                         jnp.asarray(rows), jnp.asarray(np.ones(32, bool)))
    del mask
    import jax
    att.flush_folds()
    got = np.asarray(jax.device_get(att.counts_for("PresenceGrain")))
    np.testing.assert_array_equal(got, expect)
    att.snapshot()
    assert att.stats()["stale_folds"] >= 1


# ---------------------------------------------------------------------------
# publication: silo collection, HotSet broadcast, SLO rollup
# ---------------------------------------------------------------------------

def test_silo_publishes_hot_skew_slo_and_hot_set():
    """collect_metrics mirrors the attribution snapshot into strict
    hot.*/skew.*/slo.* rows; hot_set() flattens the HotSet contract;
    the load publisher broadcasts it with the runtime statistics."""
    from orleans_tpu import metrics as m
    from orleans_tpu.runtime.load_publisher import collect_silo_statistics
    from orleans_tpu.runtime.silo import Silo

    async def go():
        silo = Silo(name="attr-silo")
        await silo.start()
        try:
            keys = np.arange(256, dtype=np.int64)
            # skew: key 0 gets 4x traffic
            skewed = np.concatenate([keys, np.zeros(768, np.int64)])
            silo.tensor_engine.send_batch(
                "PresenceGrain", "heartbeat", skewed,
                {"game": (skewed % 8).astype(np.int32),
                 "score": np.ones(len(skewed), np.float32),
                 "tick": np.full(len(skewed), 1, np.int32)})
            await silo.tensor_engine.flush()
            snap = silo.collect_metrics(force_ledger=True)
            gauges = snap["gauges"]
            for name in ("hot.grain_msgs", "hot.grain_share",
                         "hot.topk_share", "hot.confidence",
                         "skew.max_shard_share", "skew.gini",
                         "skew.p99_to_mean", "slo.healthy",
                         "slo.latency_burn_rate", "slo.drop_burn_rate"):
                assert name in gauges, f"{name} not published"
                assert name in m.CATALOG
            hot0 = [lk for lk in gauges["hot.grain_msgs"]
                    if "key=0" in lk and "arena=PresenceGrain" in lk]
            assert hot0, "the 4x-hot grain 0 missing from hot.*"
            assert snap["counters"]["slo.attempted_msgs"][""] > 0
            hs = silo.hot_set()
            assert hs and hs[0]["key"] == 0
            for h in hs:
                for field_ in ("arena", "key", "msgs", "share",
                               "sketch_est", "confidence"):
                    assert field_ in h
            stats = collect_silo_statistics(silo)
            assert stats.hot_set and stats.hot_set[0]["key"] == 0
        finally:
            await silo.stop(graceful=False)

    asyncio.run(go())


def test_live_disable_retracts_hot_set_and_gauges():
    """Live-disabling attribution must not leave the silo serving the
    pre-disable HotSet or the last-published hot.*/skew.* gauges — the
    rebalancer and dashboard would act on dead data forever."""
    from orleans_tpu.runtime.load_publisher import collect_silo_statistics
    from orleans_tpu.runtime.silo import Silo

    async def go():
        silo = Silo(name="attr-off-silo")
        await silo.start()
        try:
            keys = np.concatenate([np.arange(64, dtype=np.int64),
                                   np.zeros(256, np.int64)])
            silo.tensor_engine.send_batch(
                "PresenceGrain", "heartbeat", keys,
                {"game": (keys % 8).astype(np.int32),
                 "score": np.ones(len(keys), np.float32),
                 "tick": np.full(len(keys), 1, np.int32)})
            await silo.tensor_engine.flush()
            snap = silo.collect_metrics(force_ledger=True)
            assert snap["gauges"].get("hot.grain_msgs")
            assert silo.hot_set()
            silo.update_config({"metrics": {"attribution_enabled": False}})
            # immediate: the broadcast never serves one more stale copy
            assert silo.hot_set() == []
            assert collect_silo_statistics(silo).hot_set == []
            # next due publish retracts the gauge families
            snap2 = silo.collect_metrics(force_ledger=True)
            for name in silo._ATTRIBUTION_GAUGE_FAMILIES:
                assert not snap2["gauges"].get(name), f"{name} stale"
        finally:
            await silo.stop(graceful=False)

    asyncio.run(go())


def test_slo_burn_rate_math():
    """The drop-SLO burn: dropped/attempted over the error budget —
    checked against hand-computed numbers on a live registry."""
    from orleans_tpu import metrics as m
    from orleans_tpu.runtime.silo import Silo

    async def go():
        silo = Silo(name="slo-silo")
        silo.config.metrics.slo_drop_error_budget = 0.01
        await silo.start()
        try:
            reg = m.MetricsRegistry(source="slo-silo")
            silo._publish_slo(reg, silo.tensor_engine)
            snap = reg.snapshot()
            assert snap["gauges"]["slo.healthy"][""]["slo-silo"] == 1.0
            # synthesize drops: 5 dead letters against ~0 engine traffic
            for _ in range(5):
                silo.dead_letters.record(None, "expired")
            reg2 = m.MetricsRegistry(source="slo-silo")
            silo._publish_slo(reg2, silo.tensor_engine)
            s2 = reg2.snapshot()
            dropped = s2["counters"]["slo.dropped_msgs"][""]
            attempted = s2["counters"]["slo.attempted_msgs"][""]
            assert dropped == 5 and attempted >= 5
            burn = s2["gauges"]["slo.drop_burn_rate"][""]["slo-silo"]
            assert burn == pytest.approx(
                dropped / attempted / 0.01, rel=1e-6)
        finally:
            await silo.stop(graceful=False)

    asyncio.run(go())


# ---------------------------------------------------------------------------
# dashboard: hot/skew/slo rows, offline merge over mixed rounds
# ---------------------------------------------------------------------------

def _old_round_snapshot():
    """A registry snapshot predating this PR's catalog names."""
    from orleans_tpu import metrics as m
    reg = m.MetricsRegistry(source="old-silo")
    reg.counter("engine.messages_processed").set_total(1000)
    reg.counter("engine.ticks").set_total(10)
    reg.counter("engine.tick_seconds").set_total(1)
    return reg.snapshot()


def _new_round_snapshot():
    from orleans_tpu import metrics as m
    reg = m.MetricsRegistry(source="new-silo")
    reg.counter("engine.messages_processed").set_total(2000)
    reg.gauge("hot.grain_msgs",
              {"arena": "PresenceGrain", "key": "42"}).set(500)
    reg.gauge("hot.grain_share",
              {"arena": "PresenceGrain", "key": "42"}).set(0.25)
    reg.gauge("hot.topk_share", {"arena": "PresenceGrain"}).set(0.6)
    reg.gauge("hot.confidence", {"arena": "PresenceGrain"}).set(0.98)
    reg.gauge("skew.gini", {"arena": "PresenceGrain"}).set(0.7)
    reg.gauge("skew.max_shard_share",
              {"arena": "PresenceGrain"}).set(0.5)
    reg.gauge("skew.p99_to_mean", {"arena": "PresenceGrain"}).set(9.5)
    reg.counter("slo.latency_window_msgs").set_total(1000)
    reg.counter("slo.latency_over_budget").set_total(50)
    reg.gauge("slo.latency_error_budget").set(0.01)
    reg.gauge("slo.latency_burn_rate").set(5.0)
    reg.counter("slo.attempted_msgs").set_total(2000)
    reg.counter("slo.dropped_msgs").set_total(2)
    reg.gauge("slo.drop_error_budget").set(0.001)
    reg.gauge("slo.drop_burn_rate").set(1.0)
    reg.gauge("slo.healthy").set(0.0)
    return reg.snapshot()


def test_dashboard_renders_hot_skew_slo_rows():
    from orleans_tpu.dashboard import render_text, view_from_snapshots

    view = view_from_snapshots([_old_round_snapshot(),
                                _new_round_snapshot()])
    c = view["cluster"]
    assert c["hot_grains"][0]["key"] == "42"
    assert c["hot_grains"][0]["msgs"] == 500
    assert c["hot_grains"][0]["silo"] == "new-silo"
    assert c["skew"]["PresenceGrain"]["gini"] == 0.7
    slo = c["slo"]
    # cluster burn recomputed from SUMMED counters: 50/1000/0.01 = 5
    assert slo["latency_burn_rate"] == pytest.approx(5.0)
    assert slo["drop_burn_rate"] == pytest.approx(1.0)
    assert not slo["healthy"]
    assert slo["worst_silo"] == "new-silo"
    text = render_text(view)
    assert "hot grains:" in text and "skew:" in text
    assert "slo: BURNING" in text


def test_dashboard_file_mode_mixed_rounds(tmp_path, capsys):
    """Offline --file merge over artifacts from DIFFERENT catalog
    rounds: an older snapshot missing every new name must render, not
    KeyError (both JSON and --text)."""
    from orleans_tpu import dashboard

    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(_old_round_snapshot()))
    new.write_text(json.dumps(_new_round_snapshot()))
    assert dashboard.main(["--file", str(old), str(new)]) == 0
    view = json.loads(capsys.readouterr().out)
    assert view["cluster"]["throughput"]["engine_messages"] == 3000
    assert view["cluster"]["hot_grains"][0]["key"] == "42"
    assert dashboard.main(["--file", str(old), "--text"]) == 0
    out = capsys.readouterr().out
    assert "hot grains:" not in out  # old round alone has no hot data
    assert "msgs" in out or "cluster" in out or out.strip()
