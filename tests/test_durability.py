"""Durable state plane tests (tensor/checkpoint.py).

The contract under test, end to end:

* a FULL checkpoint is a consistent cut whose restore reconstructs
  per-key state AND row identity (key→row map, generation, eviction
  epoch, free-list high-water) exactly;
* incremental DELTAS select exactly the moved rows (attribution counts
  / clocks / key churn), never span a generation change, and compose
  with the full into the same bit-exact state;
* the device JOURNAL seals ingress batches into durable segments whose
  fold-replay reproduces an uninterrupted engine bit-for-bit at the
  acknowledged horizon — fused and unfused;
* a HARD KILL mid-traffic recovers inside the accounting invariant:
  zero acknowledged-write loss, bounded recovery time;
* the file stores are torn-write safe (tmp + fsync + atomic rename).
"""

import asyncio
import os

import jax.numpy as jnp
import numpy as np
import pytest

import samples.banking as banking
import samples.presence  # noqa: F401 — registers the presence grains
from orleans_tpu.config import TensorEngineConfig
from orleans_tpu.core.grain import batched_method, commutative
from orleans_tpu.tensor import (
    Batch,
    FileSnapshotStore,
    MemorySnapshotStore,
    MemoryVectorStore,
    TensorEngine,
    VectorGrain,
    field,
    seg_sum,
    vector_grain,
)
from orleans_tpu.tensor.vector_grain import scatter_add_rows, vector_type

pytestmark = pytest.mark.durability


def _engine(backing, **cfg_kw):
    cfg = TensorEngineConfig(tick_interval=0.0, auto_fusion_ticks=0,
                             **cfg_kw)
    return TensorEngine(config=cfg,
                        snapshot_store=MemorySnapshotStore(backing))


def _drive_presence(engine, keys, games, n_ticks, start=0):
    inj = engine.make_injector("PresenceGrain", "heartbeat", keys)
    for t in range(start, start + n_ticks):
        inj.inject({"game": games,
                    "score": np.ones(len(keys), np.float32),
                    "tick": np.int32(t + 1)})
        engine.run_tick()


def _arena_state(engine, type_name, keys):
    arena = engine.arena_for(type_name)
    rows, found = arena.lookup_rows(np.asarray(keys, dtype=np.int64))
    assert found.all()
    return {n: np.asarray(c)[rows] for n, c in arena.state.items()}


def test_full_checkpoint_restores_state_and_identity(run):
    """Kill after a sealed full checkpoint: per-key state, row ids,
    generation, eviction epoch and free-list high-water all equal the
    uninterrupted engine's."""

    async def main():
        backing = MemorySnapshotStore.shared_backing()
        eng = _engine(backing)
        keys = np.arange(300, dtype=np.int64)
        games = (keys % 7).astype(np.int32)
        _drive_presence(eng, keys, games, 6)
        await eng.flush()
        # evict a slice so free lists + epoch are non-trivial
        arena = eng.arena_for("PresenceGrain")
        arena.evict_keys(keys[250:], write_back=False)
        gen0, epoch0 = arena.generation, arena.eviction_epoch
        eng.checkpointer.checkpoint_full()

        eng2 = _engine(backing)
        stats = await eng2.checkpointer.recover()
        assert stats["recovered"]
        a2 = eng2.arena_for("PresenceGrain")
        assert a2.generation == gen0
        assert a2.eviction_epoch == epoch0
        assert a2.live_count == arena.live_count
        assert np.array_equal(a2._key_of_row, arena._key_of_row)
        assert np.array_equal(np.asarray(a2._shard_next),
                              np.asarray(arena._shard_next))
        # free lists as SETS (LIFO order is not identity)
        for f1, f2 in zip(arena._free, a2._free):
            assert set(f1.tolist()) == set(f2.tolist())
        live = keys[:250]
        s1 = _arena_state(eng, "PresenceGrain", live)
        s2 = _arena_state(eng2, "PresenceGrain", live)
        for name in s1:
            assert np.array_equal(s1[name], s2[name]), name

    run(main())


def test_journal_fold_replay_bit_exact_vs_uninterrupted(run):
    """Hard kill with sealed journal tail: the recovered engine equals
    an uninterrupted oracle engine driven with exactly the acknowledged
    command prefix — bit-exact integer state, including the transfer
    emit leg reconstructed by re-execution."""

    async def main():
        backing = MemorySnapshotStore.shared_backing()
        n_accounts = 200
        events = banking.make_events(n_accounts, 20, lanes=64, seed=7)
        eng = _engine(backing, journal_flush_every_ticks=3)
        banking.register_banking_journal(eng)
        eng.checkpointer.checkpoint_full()  # the base recovery point
        # drive WITHOUT a final flush: a flush is a quiesce and seals
        # the tail — the hard kill must land with ring lanes pending
        for ev in events:
            args = {"amount": ev["amount"]}
            if ev["method"] == "transfer":
                args["dst"] = ev["dst"]
            eng.send_batch("AccountGrain", ev["method"], ev["keys"],
                           args)
            eng.run_tick()
        site = eng.checkpointer.journal.sites[("AccountGrain",
                                               "deposit")]
        site_t = eng.checkpointer.journal.sites[("AccountGrain",
                                                 "transfer")]
        acked = (site.committed_lanes + site_t.committed_lanes) // 64
        assert 0 < acked < len(events)  # a real loss window
        # HARD KILL eng.  Oracle engine: uninterrupted, plane off,
        # driven with exactly the acknowledged prefix (seals are FIFO)
        oracle_eng = TensorEngine(config=TensorEngineConfig(
            tick_interval=0.0, auto_fusion_ticks=0))
        oracle = banking.BankOracle(n_accounts)
        await banking.run_banking_load(oracle_eng, events[:acked],
                                       oracle=oracle)
        eng2 = _engine(backing, journal_flush_every_ticks=4)
        stats = await eng2.checkpointer.recover()
        assert stats["replayed_lanes"] == acked * 64
        probe = np.arange(n_accounts, dtype=np.int64)
        # every account the oracle touched must exist + match; untouched
        # accounts must not be resident with nonzero state
        a2 = eng2.arena_for("AccountGrain")
        touched = np.unique(np.concatenate(
            [np.concatenate([e["keys"],
                             e.get("dst", np.empty(0, np.int64))])
             for e in events[:acked]])).astype(np.int64)
        got = banking.read_accounts(eng2, touched)
        want = oracle.expect(touched)
        for name in ("balance", "credits", "debits"):
            assert np.array_equal(got[name], want[name]), name
        # conservation: the restored total equals total minted
        rows_all, found_all = a2.lookup_rows(probe)
        total = int(np.asarray(a2.state["balance"])[
            rows_all[found_all]].sum())
        assert total == oracle.total()
        # and bit-exact vs the uninterrupted ENGINE too (not just the
        # numpy oracle): same fold order guarantees
        s1 = banking.read_accounts(oracle_eng, touched)
        for name in s1:
            assert np.array_equal(s1[name], got[name]), name

    run(main())


def test_delta_checkpoint_selects_moved_rows_and_composes(run):
    """Between checkpoints only touched rows re-write; full + delta
    compose into the same state a full-at-the-end would give."""

    async def main():
        backing = MemorySnapshotStore.shared_backing()
        eng = _engine(backing)
        keys = np.arange(400, dtype=np.int64)
        games = (keys % 5).astype(np.int32)
        _drive_presence(eng, keys, games, 4)
        await eng.flush()
        eng.checkpointer.checkpoint_full()
        rows_before = eng.checkpointer.rows_written
        # touch only the first 50 keys
        hot = keys[:50]
        _drive_presence(eng, hot, games[:50], 3, start=10)
        await eng.flush()
        r = eng.checkpointer.checkpoint_delta()
        assert r["kind"] == "delta"
        delta_rows = eng.checkpointer.rows_written - rows_before
        # PresenceGrain dirty = 50 hot rows; GameGrain fan-in rows are
        # dirty too (5 games) — but never the cold 350
        assert 50 <= delta_rows <= 50 + 10
        eng2 = _engine(backing)
        await eng2.checkpointer.recover()
        for t in ("PresenceGrain", "GameGrain"):
            a1, a2 = eng.arena_for(t), eng2.arena_for(t)
            assert np.array_equal(a1._key_of_row, a2._key_of_row)
            ks = a1.keys()
            s1 = _arena_state(eng, t, ks)
            s2 = _arena_state(eng2, t, ks)
            for name in s1:
                assert np.array_equal(s1[name], s2[name]), (t, name)

    run(main())


def test_delta_exact_under_evict_and_slot_reuse(run):
    """The reused-row isolation case: evict a key between checkpoints,
    let a DIFFERENT key reuse its slot, delta, kill, restore — the new
    key owns the slot with its own state, the evicted key is gone, and
    row identity matches the live engine exactly."""

    async def main():
        backing = MemorySnapshotStore.shared_backing()
        eng = _engine(backing)
        n = 120
        events = banking.make_events(n, 6, lanes=48, seed=3,
                                     transfer_every=0)
        banking.register_banking_journal(eng)
        oracle = banking.BankOracle(n)
        await banking.run_banking_load(eng, events, oracle=oracle)
        eng.checkpointer.checkpoint_full()
        arena = eng.arena_for("AccountGrain")
        victim = int(events[0]["keys"][0])
        victim_row = int(arena.lookup_rows(
            np.array([victim], np.int64))[0][0])
        arena.evict_keys(np.array([victim], np.int64), write_back=False)
        # a fresh key activates — LIFO free list hands it the slot
        newcomer = np.int64(n + 999)
        ev = {"method": "deposit",
              "keys": np.array([newcomer], np.int64),
              "amount": np.array([17], np.int32)}
        await banking.run_banking_load(eng, [ev])
        rows, found = arena.lookup_rows(np.array([newcomer]))
        assert found[0] and int(rows[0]) == victim_row  # slot reused
        eng.checkpointer.checkpoint_delta()
        eng2 = _engine(backing)
        await eng2.checkpointer.recover()
        a2 = eng2.arena_for("AccountGrain")
        assert np.array_equal(a2._key_of_row, arena._key_of_row)
        assert not a2.lookup_rows(np.array([victim], np.int64))[1][0]
        got = banking.read_accounts(eng2, np.array([newcomer]))
        assert int(got["balance"][0]) == 17
        assert int(got["credits"][0]) == 1

    run(main())


def test_generation_change_promotes_delta_to_full(run):
    """Row moves (growth) between checkpoints invalidate delta row ids
    — the plane must promote the next delta to a full."""

    async def main():
        backing = MemorySnapshotStore.shared_backing()
        eng = _engine(backing)
        keys = np.arange(64, dtype=np.int64)
        games = (keys % 4).astype(np.int32)
        _drive_presence(eng, keys, games, 3)
        await eng.flush()
        eng.checkpointer.checkpoint_full()
        fulls0 = eng.checkpointer.full_snapshots
        # force growth: activate far past capacity
        more = np.arange(64, 3000, dtype=np.int64)
        eng.arena_for("PresenceGrain").resolve_rows(more, tick=5)
        r = eng.checkpointer.checkpoint_delta()
        assert r["kind"] == "full"
        assert eng.checkpointer.full_snapshots == fulls0 + 1
        assert eng.checkpointer.delta_snapshots == 0

    run(main())


def test_fused_run_recovers_bit_exact(run):
    """The journal rides auto-fused steady state: a fused engine's
    committed horizon restores bit-exact against an unfused oracle —
    the fused/unfused equivalence the whole engine is built on, now
    surviving a crash."""

    async def main():
        backing = MemorySnapshotStore.shared_backing()
        n = 150
        rng = np.random.default_rng(11)
        keys = np.arange(n, dtype=np.int64)
        amounts = [rng.integers(1, 50, n).astype(np.int32)
                   for _ in range(24)]
        cfg = TensorEngineConfig(tick_interval=0.0, auto_fusion_ticks=4,
                                 auto_fusion_window=4,
                                 journal_flush_every_ticks=6)
        eng = TensorEngine(config=cfg,
                           snapshot_store=MemorySnapshotStore(backing))
        banking.register_banking_journal(eng)
        eng.checkpointer.checkpoint_full()
        inj = eng.make_injector("AccountGrain", "deposit", keys)
        for a in amounts:
            inj.inject({"amount": a})
            eng.run_tick()
        await eng.flush()
        assert eng.autofuser.snapshot()["windows_run"] > 0
        site = eng.checkpointer.journal.sites[("AccountGrain",
                                               "deposit")]
        acked = site.committed_lanes // n
        assert 0 < acked <= len(amounts)
        # HARD KILL.  Unfused oracle over the acknowledged prefix:
        oracle_eng = TensorEngine(config=TensorEngineConfig(
            tick_interval=0.0, auto_fusion_ticks=0))
        oinj = oracle_eng.make_injector("AccountGrain", "deposit", keys)
        for a in amounts[:acked]:
            oinj.inject({"amount": a})
            oracle_eng.run_tick()
        await oracle_eng.flush()
        eng2 = TensorEngine(config=cfg,
                            snapshot_store=MemorySnapshotStore(backing))
        await eng2.checkpointer.recover()
        s1 = banking.read_accounts(oracle_eng, keys)
        s2 = banking.read_accounts(eng2, keys)
        for name in s1:
            assert np.array_equal(s1[name], s2[name]), name

    run(main())


def test_journal_non_lane_device_leaf_appends_and_replays(run):
    """Review regression: an args leaf that is a DEVICE array whose
    first dimension differs from the batch's lane count (a per-batch
    constant, e.g. a lookup table) must append by reference like any
    device leaf — the old shape[0]==lanes guard dropped it into the
    scalar branch, crashing every send on the journaled site."""
    import jax.numpy as jnp

    async def main():
        backing = MemorySnapshotStore.shared_backing()
        eng = _engine(backing)
        eng.register_journal("PresenceGrain", "heartbeat")
        eng.checkpointer.checkpoint_full()
        keys = np.arange(16, dtype=np.int64)
        games = jnp.asarray(np.zeros(16, np.int32))
        # "score" rides as a WIDER device constant is not presentable
        # through the real handler; instead exercise the journal path
        # directly with a mixed-width tree via a raw batch append
        from orleans_tpu.tensor.checkpoint import DeviceJournal

        class FakeBatch:
            keys_host = keys
            keys_dev = None
            inject_tick = 3
            args = {"game": games,                       # lane-aligned dev
                    "table": jnp.arange(7, dtype=jnp.int32),  # non-lane dev
                    "tick": np.int32(4)}                 # scalar

        eng.checkpointer.journal.append("PresenceGrain", "heartbeat",
                                        FakeBatch)
        eng.checkpointer.journal.flush()
        manifest = eng.checkpointer.store.read_manifest()
        seg = manifest["journal"]["PresenceGrain.heartbeat"]["segments"][-1]
        arrays, meta = eng.checkpointer.store.get_blob(seg["blob"])
        entries = DeviceJournal.decode_segment(arrays, meta)
        e = entries[-1]
        assert np.array_equal(e["keys"], keys)
        assert np.array_equal(e["args"]["game"], np.zeros(16, np.int32))
        assert np.array_equal(e["args"]["table"], np.arange(7))
        assert int(e["args"]["tick"]) == 4

    run(main())


def test_delta_restore_applies_recorded_use_clocks(run):
    """Review regression: a delta's meta records the FULL host use
    clock at its cut — restore must apply it, or rows hot at the crash
    keep the BASE snapshot's stale clocks and the first idle sweep
    after recovery evicts them as idle."""

    async def main():
        backing = MemorySnapshotStore.shared_backing()
        eng = _engine(backing)
        keys = np.arange(100, dtype=np.int64)
        games = (keys % 4).astype(np.int32)
        _drive_presence(eng, keys, games, 3)
        await eng.flush()
        eng.checkpointer.checkpoint_full()
        arena = eng.arena_for("PresenceGrain")
        # advance the clock far past the base, touch a hot subset
        eng.tick_number += 500
        hot = keys[:20]
        arena.resolve_rows(hot, tick=eng.tick_number)
        r = eng.checkpointer.checkpoint_delta()
        assert r["kind"] == "delta"
        eng2 = _engine(backing)
        await eng2.checkpointer.recover()
        a2 = eng2.arena_for("PresenceGrain")
        hot_rows = arena.lookup_rows(hot)[0]
        assert np.array_equal(a2.last_use_tick[hot_rows],
                              arena.last_use_tick[hot_rows])
        assert int(a2.last_use_tick[hot_rows].min()) >= 500

    run(main())


def test_periodic_cadence_commits_under_live_traffic(run):
    """The on_tick cadence path: fulls + deltas + journal seals commit
    while traffic keeps flowing; the recovery-point age stays bounded
    by the delta cadence once the first full lands."""

    async def main():
        backing = MemorySnapshotStore.shared_backing()
        eng = _engine(backing, ckpt_full_every_ticks=12,
                      ckpt_delta_every_ticks=4,
                      ckpt_pause_budget_s=0.002,
                      journal_flush_every_ticks=3)
        banking.register_banking_journal(eng)
        events = banking.make_events(100, 40, lanes=32, seed=5)
        await banking.run_banking_load(eng, events)
        ck = eng.checkpointer
        assert ck.full_snapshots >= 1
        assert ck.delta_snapshots >= 1
        assert ck.journal.segments_committed >= 1
        assert 0 <= ck.age_ticks() <= 3 * 12
        snap = eng.snapshot()["durability"]
        assert snap["enabled"] and snap["rows_written"] > 0

    run(main())


def test_journal_ring_overflow_seals_midtick_without_loss(run):
    """A full ring seals the open segment mid-append instead of
    dropping or erroring; every lane stays acknowledged-or-pending."""

    async def main():
        backing = MemorySnapshotStore.shared_backing()
        eng = _engine(backing, journal_ring_lanes=128)
        banking.register_banking_journal(eng)
        events = banking.make_events(50, 10, lanes=48, seed=9,
                                     transfer_every=0)
        await banking.run_banking_load(eng, events)
        j = eng.checkpointer.journal
        assert j.ring_overflows > 0
        site = j.sites[("AccountGrain", "deposit")]
        assert site.appended_lanes == 10 * 48
        assert site.appended_lanes == site.committed_lanes \
            + site.segment_lanes

    run(main())


def test_file_snapshot_store_roundtrip_and_atomic_manifest(run, tmp_path):
    """The on-disk store: a full kill→recover round trip through real
    files, and a crash INSIDE a manifest commit leaves the previous
    recovery point readable (atomic replace)."""

    async def main():
        root = str(tmp_path / "snaps")
        eng = TensorEngine(config=TensorEngineConfig(
            tick_interval=0.0, auto_fusion_ticks=0),
            snapshot_store=FileSnapshotStore(root))
        banking.register_banking_journal(eng)
        events = banking.make_events(80, 8, lanes=32, seed=2)
        oracle = banking.BankOracle(80)
        await banking.run_banking_load(eng, events, oracle=oracle)
        eng.checkpointer.checkpoint_full()
        # crash mid-commit: os.replace raises before the swap — the
        # OLD manifest must stay intact and readable
        store = FileSnapshotStore(root)
        good = store.read_manifest()
        assert good is not None
        real_replace = os.replace

        def boom(src, dst):
            if dst.endswith("MANIFEST.json"):
                raise OSError("injected kill mid-commit")
            return real_replace(src, dst)

        os.replace = boom
        try:
            with pytest.raises(OSError):
                store.commit_manifest({"seq": 10**6, "recovery": None})
        finally:
            os.replace = real_replace
        assert store.read_manifest() == good
        eng2 = TensorEngine(config=TensorEngineConfig(
            tick_interval=0.0, auto_fusion_ticks=0),
            snapshot_store=FileSnapshotStore(root))
        stats = await eng2.checkpointer.recover()
        assert stats["recovered"]
        touched = np.unique(np.concatenate(
            [e["keys"] for e in events])).astype(np.int64)
        got = banking.read_accounts(eng2, touched)
        want = oracle.expect(touched)
        for name in ("balance", "credits", "debits"):
            assert np.array_equal(got[name], want[name]), name

    run(main())


def test_file_vector_store_torn_write_leaves_prior_record(tmp_path):
    """The FileVectorStore crash-safety regression: an exception thrown
    mid-columnar-write (the chaos storage seam's fault shape) leaves
    the previously committed record readable and no torn final path."""
    from orleans_tpu.tensor.persistence import FileVectorStore

    store = FileVectorStore(str(tmp_path / "rows"))
    keys = [1, 2, 3]
    cols = {"balance": np.array([10, 20, 30], np.int32)}
    store.write_many_columnar("Acct", keys, cols)
    calls = {"n": 0}
    real_savez = np.savez

    def flaky(f, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise OSError("injected kill mid-write")
        return real_savez(f, **kw)

    np.savez = flaky
    try:
        with pytest.raises(OSError):
            store.write_many_columnar(
                "Acct", keys,
                {"balance": np.array([11, 21, 31], np.int32)})
    finally:
        np.savez = real_savez
    out = store.read_many("Acct", keys)
    # key 1 committed the new value, key 2 kept the OLD one (never a
    # torn file), key 3 untouched by the interrupted pass
    assert int(out[1]["balance"]) == 11
    assert int(out[2]["balance"]) == 20
    assert int(out[3]["balance"]) == 30
    d = str(tmp_path / "rows" / "Acct")
    assert not [f for f in os.listdir(d) if f.endswith(".tmp")]


def test_durability_accounting_invariant_catches_missing_blob(run):
    """The chaos checker fails loudly when a manifest references a blob
    that is gone (the commit-order contract's tripwire)."""

    async def main():
        from orleans_tpu.chaos.invariants import (
            InvariantViolation,
            check_durability_accounting,
        )
        backing = MemorySnapshotStore.shared_backing()
        eng = _engine(backing)
        banking.register_banking_journal(eng)
        events = banking.make_events(40, 4, lanes=16, seed=1)
        await banking.run_banking_load(eng, events)
        eng.checkpointer.checkpoint_full()
        check_durability_accounting(eng)  # green
        manifest = eng.checkpointer.store.read_manifest()
        blob = manifest["recovery"]["full"]["arenas"][
            "AccountGrain"]["parts"][0]
        eng.checkpointer.store.delete_blob(blob)
        with pytest.raises(InvariantViolation):
            check_durability_accounting(eng)

    run(main())


def test_chaos_kill_scenario_green(run):
    """The seeded kill-mid-traffic scenario the chaos smoke runs: zero
    acknowledged loss, RTO met, journal replay + loss window both
    exercised."""

    async def main():
        from orleans_tpu.chaos.report import durability_kill_scenario

        report = await durability_kill_scenario(20260804)
        assert report["ok"]
        assert report["recovery"]["replayed_lanes"] > 0
        assert report["lost_unacknowledged_entries"] > 0

    run(main())


def test_silo_startup_recovery_and_graceful_stop(run):
    """The silo wiring: a graceful stop commits a terminal recovery
    point; a NEW silo over the same backing restores it during start()
    — before serving traffic — and reports the recovery stats."""

    async def main():
        from orleans_tpu.testing.cluster import TestingCluster

        backing = MemorySnapshotStore.shared_backing()

        def setup(silo):
            silo.tensor_engine.checkpointer.attach_store(
                MemorySnapshotStore(backing))
            banking.register_banking_journal(silo.tensor_engine)

        cluster = await TestingCluster(n_silos=1,
                                       silo_setup=setup).start()
        try:
            eng = cluster.silos[0].tensor_engine
            events = banking.make_events(60, 6, lanes=24, seed=4)
            oracle = banking.BankOracle(60)
            await banking.run_banking_load(eng, events, oracle=oracle)
        finally:
            await cluster.stop()  # graceful → terminal full snapshot
        cluster2 = await TestingCluster(n_silos=1,
                                        silo_setup=setup).start()
        try:
            silo = cluster2.silos[0]
            assert silo.last_recovery is not None
            assert silo.last_recovery["recovered"]
            touched = np.unique(np.concatenate(
                [np.concatenate([e["keys"],
                                 e.get("dst", np.empty(0, np.int64))])
                 for e in events])).astype(np.int64)
            got = banking.read_accounts(silo.tensor_engine, touched)
            want = oracle.expect(touched)
            for name in ("balance", "credits", "debits"):
                assert np.array_equal(got[name], want[name]), name
        finally:
            await cluster2.stop()

    run(main())


def test_silo_publishes_ckpt_and_journal_metrics(run):
    """Strict catalog publication: a plane-enabled silo's
    collect_metrics emits the ckpt.*/journal.* rows, and the dashboard
    renders the durability line from the merged snapshot."""

    async def main():
        from orleans_tpu.dashboard import render_text, view_from_snapshots
        from orleans_tpu.testing.cluster import TestingCluster

        backing = MemorySnapshotStore.shared_backing()

        def setup(silo):
            silo.tensor_engine.checkpointer.attach_store(
                MemorySnapshotStore(backing))
            banking.register_banking_journal(silo.tensor_engine)

        cluster = await TestingCluster(n_silos=1,
                                       silo_setup=setup).start()
        try:
            silo = cluster.silos[0]
            eng = silo.tensor_engine
            events = banking.make_events(50, 5, lanes=20, seed=6)
            await banking.run_banking_load(eng, events)
            eng.checkpointer.checkpoint_full()
            snap = silo.collect_metrics()
            assert snap["counters"]["ckpt.full_snapshots"][""] >= 1
            assert snap["counters"]["journal.segments"][""] >= 1
            assert "ckpt.age_ticks" in snap["gauges"]
            view = view_from_snapshots([snap])
            du = view["cluster"]["durability"]
            assert du["full_snapshots"] >= 1
            assert du["rows_written"] > 0
            text = render_text(view)
            assert "durability:" in text
        finally:
            await cluster.stop()

    run(main())


# ---------------------------------------------------------------------------
# fused fold-replay, composed recovery, warm standby (PR 18)
# ---------------------------------------------------------------------------


def _define_composed_grains():
    if vector_type("DuraCounter") is not None:
        return

    @vector_grain
    class DuraCounter(VectorGrain):
        # commutative so the grain is replicable mid-interval
        total = field(jnp.int32, 0)

        @batched_method
        @staticmethod
        @commutative
        def bump(state, batch: Batch, n_rows: int):
            return {**state,
                    "total": state["total"]
                    + seg_sum(batch.args["amount"], batch.rows,
                              n_rows)}, None, ()

    @vector_grain
    class DuraTimerProbe(VectorGrain):
        fires = field(jnp.int32, 0)

        @batched_method
        @staticmethod
        def receive_reminder(state, batch: Batch, n_rows: int):
            ones = jnp.where(batch.mask, 1, 0).astype(jnp.int32)
            return {"fires": scatter_add_rows(state["fires"],
                                              batch.rows, ones)}

        @batched_method
        @staticmethod
        def poke(state, batch: Batch, n_rows: int):
            return state


_define_composed_grains()


def _touched_keys(events):
    return np.unique(np.concatenate(
        [np.concatenate([e["keys"],
                         e.get("dst", np.empty(0, np.int64))])
         for e in events])).astype(np.int64)


def test_fused_fold_replay_matches_per_tick_and_oracle(run):
    """Fused fold-replay (stacked [T, m] windows through
    FusedTickProgram.replay — ONE compiled program per window of
    consecutive journaled ticks) is bit-exact vs BOTH the per-tick
    replay path and the uninterrupted oracle, including the transfer
    emit leg, and the fusion actually engages."""

    async def main():
        backing = MemorySnapshotStore.shared_backing()
        n_accounts = 300
        events = banking.make_events(n_accounts, 30, lanes=64, seed=29)
        eng = _engine(backing, journal_flush_every_ticks=4)
        banking.register_banking_journal(eng)
        eng.checkpointer.checkpoint_full()
        for ev in events:
            args = {"amount": ev["amount"]}
            if ev["method"] == "transfer":
                args["dst"] = ev["dst"]
            eng.send_batch("AccountGrain", ev["method"], ev["keys"],
                           args)
            eng.run_tick()
        sites = eng.checkpointer.journal.sites
        acked = (sites[("AccountGrain", "deposit")].committed_lanes
                 + sites[("AccountGrain", "transfer")].committed_lanes
                 ) // 64
        assert 0 < acked < len(events)
        oracle = banking.BankOracle(n_accounts)
        for ev in events[:acked]:
            oracle.apply(ev)
        # HARD KILL → fused recovery (default window).  The restarted
        # process re-runs its app wiring first — registration carries
        # the emit_key_args hints the fused pre-activation needs.
        eng2 = _engine(backing, journal_flush_every_ticks=4)
        banking.register_banking_journal(eng2)
        stats2 = await eng2.checkpointer.recover()
        assert stats2["recovered"]
        assert stats2["replayed_lanes"] == acked * 64
        assert stats2["fused_windows"] > 0, \
            "fusion never engaged (every window fell back per-tick)"
        assert stats2["fused_lanes"] > 0
        # per-tick recovery over the SAME manifest: defer-re-anchor
        # left the recovery point untouched, so a second recovery
        # replays the identical tail
        eng3 = _engine(backing, journal_flush_every_ticks=4,
                       recover_fused_window=1)
        banking.register_banking_journal(eng3)
        stats3 = await eng3.checkpointer.recover()
        assert stats3["fused_windows"] == 0
        assert stats3["replayed_lanes"] == acked * 64
        touched = _touched_keys(events[:acked])
        want = oracle.expect(touched)
        got2 = banking.read_accounts(eng2, touched)
        got3 = banking.read_accounts(eng3, touched)
        for name in ("balance", "credits", "debits"):
            assert np.array_equal(got2[name], want[name]), name
            assert np.array_equal(got3[name], got2[name]), name

    run(main())


def test_composed_recovery_replication_pins_timers(run):
    """Restore identity under composition — a kill/recover spanning a
    promoted replication interval, migrated pins AND armed timers in
    ONE scenario: exact state vs the acknowledged-prefix oracle
    (replica folds exact), pins survive, timers fire exactly once."""

    async def main():
        backing = MemorySnapshotStore.shared_backing()
        cfg = dict(ckpt_full_every_ticks=10, ckpt_delta_every_ticks=5,
                   ckpt_pause_budget_s=0.002, journal_flush_every_ticks=3)
        eng = _engine(backing, **cfg)
        eng.n_shards = 4
        eng.register_journal("DuraCounter", "bump")
        rng = np.random.default_rng(23)
        keys = np.arange(96, dtype=np.int64)
        hot = 7
        # arm one-shots due AFTER the whole drive: they must survive
        # the kill ARMED and fire exactly once post-recovery
        tkeys = np.arange(32, dtype=np.int64)
        inj = eng.make_injector("DuraTimerProbe", "poke", tkeys)
        inj.inject({})
        eng.run_tick()
        due = eng.tick_number + 60
        eng.timers.arm_batch("DuraTimerProbe", tkeys,
                             np.full(32, due, np.int64), 0, "close")
        amounts_by_tick = []
        for t in range(25):
            amounts = rng.integers(1, 100, 96).astype(np.int32)
            amounts_by_tick.append(amounts)
            eng.send_batch("DuraCounter", "bump", keys,
                           {"amount": amounts})
            eng.run_tick()
            if t == 5:
                assert eng.replicate_key("DuraCounter", hot, 3) == 3
            if t == 9:
                movers = rng.choice(keys, 24, replace=False)
                eng.migrate_keys("DuraCounter", movers,
                                 rng.integers(0, 4, 24))
        await eng.flush()
        arena = eng.arenas["DuraCounter"]
        pins = dict(arena._shard_override)
        assert pins and arena._replicas, "scenario degenerate"
        site = eng.checkpointer.journal.sites[("DuraCounter", "bump")]
        acked = site.committed_lanes // 96
        assert 0 < acked < 25, "kill must land mid-cadence"
        oracle = np.zeros(96, dtype=np.int64)
        for amounts in amounts_by_tick[:acked]:
            oracle += amounts
        # HARD KILL → fresh engine over the same backing
        eng2 = _engine(backing, **cfg)
        eng2.n_shards = 4
        stats = await eng2.checkpointer.recover()
        assert stats["recovered"]
        # timers armed at the cut force the per-tick replay path
        assert stats["fused_windows"] == 0
        a2 = eng2.arenas["DuraCounter"]
        # replica folds exact: read through the fold-aware accessor
        got = np.array([int(a2.read_row(int(k))["total"])
                        for k in keys], dtype=np.int64)
        assert np.array_equal(got, oracle)
        # migration pins survive recovery
        assert a2._shard_override == pins
        # the armed set survived the kill; fires exactly once, on time
        assert eng2.timers.armed_total == 32
        while eng2.tick_number < due:
            eng2.run_tick()
        await eng2.flush()
        ta = eng2.arena_for("DuraTimerProbe")
        rows, found = ta.lookup_rows(tkeys)
        assert found.all()
        fires = np.asarray(ta.state["fires"])[rows]
        assert (fires == 1).all(), fires
        for _ in range(8):
            eng2.run_tick()
        await eng2.flush()
        fires = np.asarray(ta.state["fires"])[ta.lookup_rows(tkeys)[0]]
        assert (fires == 1).all(), "timer fired twice"

    run(main())


def test_standby_tails_promotes_and_fences(run):
    """Warm standby end to end: the tailer adopts the primary's
    committed fulls/deltas and stages sealed journal segments while
    traffic runs; promotion fences the store, replays ONLY the
    un-adopted tail, lands bit-exact at the acknowledged prefix; the
    old (merely partitioned) primary can never commit again, and the
    promoted standby serves and commits from there on."""

    async def main():
        from orleans_tpu.tensor.checkpoint import (
            FencedError,
            StandbyTailer,
        )
        backing = MemorySnapshotStore.shared_backing()
        n_accounts = 200
        events = banking.make_events(n_accounts, 24, lanes=64, seed=13)
        primary = _engine(backing, ckpt_full_every_ticks=8,
                          ckpt_delta_every_ticks=4,
                          ckpt_pause_budget_s=0.002,
                          journal_flush_every_ticks=3)
        banking.register_banking_journal(primary)
        standby_eng = TensorEngine(config=TensorEngineConfig(
            tick_interval=0.0, auto_fusion_ticks=0))
        banking.register_banking_journal(standby_eng)
        tailer = StandbyTailer(standby_eng,
                               MemorySnapshotStore(backing))
        for i, ev in enumerate(events):
            args = {"amount": ev["amount"]}
            if ev["method"] == "transfer":
                args["dst"] = ev["dst"]
            primary.send_batch("AccountGrain", ev["method"],
                               ev["keys"], args)
            primary.run_tick()
            if i % 4 == 3:
                tailer.poll()
        await primary.flush()
        assert tailer.lag_ticks() >= 0
        assert tailer.adopted_rows > 0, "standby never adopted a cut"
        sites = primary.checkpointer.journal.sites
        acked = (sites[("AccountGrain", "deposit")].committed_lanes
                 + sites[("AccountGrain", "transfer")].committed_lanes
                 ) // 64
        assert 0 < acked <= len(events)
        oracle = banking.BankOracle(n_accounts)
        for ev in events[:acked]:
            oracle.apply(ev)
        # HARD KILL the primary (the OBJECT stays alive to model a
        # partitioned zombie).  Promote the standby.
        res = await tailer.promote(owner="standby-1")
        assert res["promoted"] and tailer.promoted
        assert res["fence_epoch"] >= 1
        assert standby_eng.checkpointer.promotions == 1
        touched = _touched_keys(events[:acked])
        got = banking.read_accounts(standby_eng, touched)
        want = oracle.expect(touched)
        for name in ("balance", "credits", "debits"):
            assert np.array_equal(got[name], want[name]), name
        # zero acknowledged-write loss AND the old primary is fenced:
        # its next commit over the claimed store must refuse
        with pytest.raises(FencedError):
            primary.checkpointer.checkpoint_full()
        assert primary.checkpointer.fenced
        # the promoted standby serves and commits (it owns the fence)
        standby_eng.send_batch("AccountGrain", "deposit",
                               np.arange(8, dtype=np.int64),
                               {"amount": np.ones(8, np.int32)})
        standby_eng.run_tick()
        await standby_eng.flush()
        anchor = standby_eng.checkpointer.checkpoint_full()
        assert anchor["rows"] > 0

    run(main())
