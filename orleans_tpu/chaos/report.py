"""Chaos smoke runner: one seeded plan → one JSON fault/invariant report.

``python -m orleans_tpu.chaos [--seed N] [--out PATH] [--repeat N]``
runs the canonical short scenario on a 3-silo ChaosCluster — storage
flakes + injected CAS conflicts + one NaN-poisoned slab under live
traffic, then partition → heal → hard-kill — checks all nine invariants
(including the durable-state-plane kill-mid-traffic recovery scenario),
and emits a JSON report (``CHAOS_SMOKE.json`` by default).  The report
carries the (seed, plan) pair and the deterministic trace signature, so
a failing run is replayable exactly; ``--repeat 2`` re-runs the plan and
asserts the signatures are identical (the reproducibility proof from the
acceptance criteria).
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Any, Dict, List

from orleans_tpu import Grain, StatefulGrain, grain_interface
from orleans_tpu.core.grain import grain_class
from orleans_tpu.streams.core import implicit_stream_subscription

#: process-wide delivery registry for the smoke's stream consumers —
#: survives consumer re-activation after a kill (what the at-least-once
#: checker reads)
DELIVERED: Dict[int, List[Any]] = {}


@grain_interface
class IChaosKv:
    async def put(self, v) -> None: ...
    async def save(self) -> None: ...
    async def get(self): ...
    async def slow_echo(self, v): ...


@grain_class(storage_provider="Default",
             initial_state=lambda: {"v": None})
class ChaosKvGrain(StatefulGrain, IChaosKv):
    """Host-grain traffic source: exercises RPC + the storage write seam."""

    async def put(self, v) -> None:
        self.state["v"] = v

    async def save(self) -> None:
        await self.write_state()

    async def get(self):
        return self.state["v"]

    async def slow_echo(self, v):
        # holds the executing silo long enough that a batched fabric
        # result is still outstanding when the chaos plan kills it
        await asyncio.sleep(0.25)
        return v


@grain_interface
class IChaosStreamEater:
    async def seen(self) -> list: ...


@implicit_stream_subscription("chaos-events")
@grain_class
class ChaosStreamEater(Grain, IChaosStreamEater):
    """Implicit subscriber on the smoke's stream namespace: implicit
    subscriptions survive re-activation on another silo after a kill, so
    delivery keeps flowing without a re-join step."""

    async def on_stream_item(self, stream_id, item, seq) -> None:
        DELIVERED.setdefault(int(stream_id.key), []).append(item)

    async def seen(self) -> list:
        return list(DELIVERED.get(int(self.grain_id.primary_key_int), []))


def define_chaos_counter() -> None:
    """Register the smoke's vector grain (lazy: keeps jax out of --help).
    Idempotent across runs in one process."""
    import jax.numpy as jnp

    from orleans_tpu.tensor import Batch, VectorGrain, field, seg_sum
    from orleans_tpu.tensor.vector_grain import (
        batched_method,
        vector_grain,
        vector_type,
    )

    if vector_type("ChaosCounter") is not None:
        return

    @vector_grain
    class ChaosCounter(VectorGrain):
        total = field(jnp.float32, 0.0)
        count = field(jnp.int32, 0)
        reminders = field(jnp.int32, 0)

        @batched_method
        @staticmethod
        def poke(state, batch: Batch, n_rows: int):
            live = (batch.rows >= 0)
            return {
                **state,
                "total": state["total"] + seg_sum(batch.args["v"],
                                                  batch.rows, n_rows),
                "count": state["count"] + seg_sum(
                    live.astype(jnp.int32), batch.rows, n_rows),
            }, None, ()

        @batched_method
        @staticmethod
        def receive_reminder(state, batch: Batch, n_rows: int):
            # the timers-plane delivery target (a device timer refuses
            # to arm on a type without this handler) — counts firings so
            # chaos scenarios can oracle exactly-once delivery
            live = (batch.rows >= 0)
            return {
                **state,
                "reminders": state["reminders"] + seg_sum(
                    live.astype(jnp.int32), batch.rows, n_rows),
            }, None, ()


def define_chaos_ledger() -> None:
    """Register the durability scenario's vector grain: an INTEGER
    balance ledger (integer folds are bit-exact under any replay
    grouping — the oracle compares with array_equal, not allclose).
    Idempotent across runs in one process."""
    import jax.numpy as jnp

    from orleans_tpu.tensor import Batch, VectorGrain, field, seg_sum
    from orleans_tpu.tensor.vector_grain import (
        batched_method,
        vector_grain,
        vector_type,
    )

    if vector_type("ChaosLedger") is not None:
        return

    @vector_grain
    class ChaosLedger(VectorGrain):
        balance = field(jnp.int32, 0)
        deposits = field(jnp.int32, 0)

        @batched_method
        @staticmethod
        def deposit(state, batch: Batch, n_rows: int):
            live = (batch.rows >= 0)
            return {
                **state,
                "balance": state["balance"]
                + seg_sum(batch.args["amount"], batch.rows, n_rows),
                "deposits": state["deposits"]
                + seg_sum(live.astype(jnp.int32), batch.rows, n_rows),
            }, None, ()


async def durability_kill_scenario(seed: int,
                                   rto_bound_s: float = 15.0
                                   ) -> Dict[str, Any]:
    """The durable-state-plane smoke: seeded deposit traffic over a
    journaled ledger with periodic full/delta checkpoints, a HARD KILL
    mid-traffic (the engine object is abandoned — no flush, no
    goodbye), then recovery on a fresh engine over the same durable
    backing.  Asserts ``check_durability_accounting``: manifest/blob
    integrity, journal counter algebra, recovery inside the RTO bound,
    and ZERO acknowledged-write loss — restored balances equal the host
    oracle folded over exactly the acknowledged (sealed) event prefix.
    """
    import numpy as np

    from orleans_tpu.chaos.invariants import check_durability_accounting
    from orleans_tpu.config import TensorEngineConfig
    from orleans_tpu.tensor import MemorySnapshotStore, TensorEngine

    define_chaos_ledger()
    backing = MemorySnapshotStore.shared_backing()
    # cadences chosen so the kill lands MID-cadence: the last recovery
    # point sits several ticks back, sealed journal segments extend past
    # it (recovery must fold-replay them), and the final entries are
    # still in the ring (the documented, nonzero loss window)
    cfg = TensorEngineConfig(
        tick_interval=0.0, auto_fusion_ticks=0,
        ckpt_full_every_ticks=10, ckpt_delta_every_ticks=5,
        ckpt_pause_budget_s=0.002, journal_flush_every_ticks=3)
    engine = TensorEngine(config=cfg,
                          snapshot_store=MemorySnapshotStore(backing))
    engine.register_journal("ChaosLedger", "deposit")
    rng = np.random.default_rng(seed)
    n_keys = 64
    keys = np.arange(n_keys, dtype=np.int64)
    ticks_driven = 29
    amounts_by_entry: List[np.ndarray] = []
    for _ in range(ticks_driven):
        amounts = rng.integers(1, 100, n_keys).astype(np.int32)
        amounts_by_entry.append(amounts)
        engine.send_batch("ChaosLedger", "deposit", keys,
                          {"amount": amounts})
        engine.run_tick()
    await engine.flush()
    site = engine.checkpointer.journal.sites[("ChaosLedger", "deposit")]
    # HARD KILL: nothing else runs on `engine` — pending ring lanes and
    # any un-drained snapshot die with it.  The acknowledged horizon is
    # the sealed prefix (seals are FIFO, one entry per driven tick).
    acked_entries = site.committed_lanes // n_keys
    assert site.committed_lanes == acked_entries * n_keys
    oracle = np.zeros(n_keys, dtype=np.int64)
    for amounts in amounts_by_entry[:acked_entries]:
        oracle += amounts
    expected = {("ChaosLedger", int(k)): {
        "balance": np.int32(oracle[k]),
        "deposits": np.int32(acked_entries)} for k in keys}
    engine2 = TensorEngine(config=cfg,
                           snapshot_store=MemorySnapshotStore(backing))
    stats = await engine2.checkpointer.recover()
    report = check_durability_accounting(
        engine2, expected=expected, recover_stats=stats,
        rto_bound_s=rto_bound_s)
    # the scenario must exercise BOTH interesting paths: sealed journal
    # entries past the recovery point (fold-replay ran) and unsealed
    # ring entries (a real, nonzero loss window was excluded)
    assert stats["replayed_lanes"] > 0, \
        "scenario degenerate: recovery replayed no journal tail"
    assert ticks_driven > acked_entries, \
        "scenario degenerate: every entry was already acknowledged"
    report.update({
        "driven_entries": ticks_driven,
        "acknowledged_entries": acked_entries,
        "lost_unacknowledged_entries": ticks_driven - acked_entries,
        "recovery": {k: v for k, v in stats.items() if k != "re_anchor"},
    })
    return report


async def standby_failover_scenario(seed: int,
                                    rto_bound_s: float = 15.0
                                    ) -> Dict[str, Any]:
    """Warm-standby failover smoke: a standby engine tails the
    primary's committed fulls/deltas and stages its sealed journal
    segments WHILE seeded deposit traffic runs; the primary is
    hard-killed mid-cadence and the standby promotes — fence the
    store, fold-replay only the un-adopted tail, land bit-exact at
    the acknowledged prefix.  Asserts zero acknowledged-write loss,
    promotion inside the RTO bound, and that the old (merely
    partitioned, still-running) primary can never commit again once
    its range is claimed."""
    import numpy as np

    from orleans_tpu.config import TensorEngineConfig
    from orleans_tpu.tensor import MemorySnapshotStore, TensorEngine
    from orleans_tpu.tensor.checkpoint import FencedError, StandbyTailer

    define_chaos_ledger()
    backing = MemorySnapshotStore.shared_backing()
    cfg = TensorEngineConfig(
        tick_interval=0.0, auto_fusion_ticks=0,
        ckpt_full_every_ticks=10, ckpt_delta_every_ticks=5,
        ckpt_pause_budget_s=0.002, journal_flush_every_ticks=3)
    primary = TensorEngine(config=cfg,
                           snapshot_store=MemorySnapshotStore(backing))
    primary.register_journal("ChaosLedger", "deposit")
    standby = TensorEngine(config=TensorEngineConfig(
        tick_interval=0.0, auto_fusion_ticks=0))
    standby.register_journal("ChaosLedger", "deposit")
    tailer = StandbyTailer(standby, MemorySnapshotStore(backing))
    rng = np.random.default_rng(seed)
    n_keys = 64
    keys = np.arange(n_keys, dtype=np.int64)
    ticks_driven = 29
    amounts_by_entry: List[np.ndarray] = []
    for t in range(ticks_driven):
        amounts = rng.integers(1, 100, n_keys).astype(np.int32)
        amounts_by_entry.append(amounts)
        primary.send_batch("ChaosLedger", "deposit", keys,
                           {"amount": amounts})
        primary.run_tick()
        if t % 3 == 2:
            tailer.poll()  # log shipping rides the committed cuts
    await primary.flush()
    assert tailer.adopted_rows > 0, \
        "scenario degenerate: standby never adopted a committed cut"
    site = primary.checkpointer.journal.sites[("ChaosLedger",
                                               "deposit")]
    # HARD KILL the primary process; the OBJECT stays alive to model
    # the partitioned zombie the fence must reject
    acked_entries = site.committed_lanes // n_keys
    oracle = np.zeros(n_keys, dtype=np.int64)
    for amounts in amounts_by_entry[:acked_entries]:
        oracle += amounts
    res = await tailer.promote(owner="chaos-standby")
    assert res["promoted"]
    assert res["replayed_lanes"] > 0, \
        "scenario degenerate: promotion replayed no journal tail"
    assert ticks_driven > acked_entries, \
        "scenario degenerate: every entry was already acknowledged"
    rto_s = res["seconds"]
    if rto_s > rto_bound_s:
        from orleans_tpu.chaos.invariants import InvariantViolation
        raise InvariantViolation(
            f"standby promotion took {rto_s:.3f}s > bound "
            f"{rto_bound_s}s")
    # zero acknowledged-write loss, bit-exact at the acked horizon
    arena = standby.arena_for("ChaosLedger")
    rows, found = arena.lookup_rows(keys)
    assert found.all(), "promoted standby lost acknowledged accounts"
    balances = np.asarray(arena.state["balance"])[rows].astype(np.int64)
    deposits = np.asarray(arena.state["deposits"])[rows]
    assert np.array_equal(balances, oracle), \
        "promoted standby balances diverge from the acked oracle"
    assert (deposits == acked_entries).all(), \
        "promoted standby deposit counts diverge"
    # promotion fence: the old primary's next commit must refuse, and
    # its plane must report itself fenced (a silo wires this to kill)
    fenced = False
    try:
        primary.checkpointer.checkpoint_full()
    except FencedError:
        fenced = True
    assert fenced, "old primary committed after its range was claimed"
    assert primary.checkpointer.fenced
    return {
        "ok": True,
        "driven_entries": ticks_driven,
        "acknowledged_entries": acked_entries,
        "lost_unacknowledged_entries": ticks_driven - acked_entries,
        "rto_s": round(rto_s, 6),
        "rto_bound_s": rto_bound_s,
        "fence_epoch": res["fence_epoch"],
        "adopted_rows": res["adopted_rows"],
        "replayed_lanes": res["replayed_lanes"],
        "old_primary_fenced": True,
    }


async def migration_storm_scenario(seed: int,
                                   pause_bound_s: float = 2.0
                                   ) -> Dict[str, Any]:
    """The closed-loop rebalance plane's storm smoke: forced MASS
    MIGRATION during traffic, at both granularities.

    Leg 1 (intra-engine): seeded deposit traffic over a 4-shard-block
    ledger arena interleaved with random mass-migration waves
    (``engine.migrate_keys`` — shard blocks are a logical row layout,
    so this leg is deterministic on any device count), then
    ``check_mesh_single_activation`` (placement honors the migration
    pins) and balances asserted EXACTLY equal to a never-migrated
    oracle engine fed the same injection sequence — migration moves
    rows, never state.

    Leg 2 (cluster): deposit traffic over a 2-silo in-proc cluster
    with cross-silo migration waves (override broadcast + state-slab
    adoption), a silo JOIN mid-traffic (ring-change handoff pushes the
    moved keys' state), and a graceful DRAIN (the leaver migrates its
    residents out) — single-activation across survivors, zero
    acknowledged-write loss vs the host oracle over every
    (quiesce-acknowledged) deposit, every per-wave migration pause
    under ``pause_bound_s`` (after a warm wave absorbs the one-time
    kernel compiles)."""
    import time as _time

    import numpy as np

    from orleans_tpu.chaos.invariants import (
        InvariantViolation,
        check_mesh_single_activation,
    )
    from orleans_tpu.config import TensorEngineConfig
    from orleans_tpu.tensor import TensorEngine

    define_chaos_ledger()
    rng = np.random.default_rng(seed)
    pauses: List[float] = []

    def _balances(engine, keys) -> np.ndarray:
        arena = engine.arenas["ChaosLedger"]
        rows, found = arena.lookup_rows(keys)
        if not found.all():
            raise InvariantViolation(
                f"migration storm: {int((~found).sum())} keys lost")
        return np.asarray(arena.state["balance"])[rows]

    # ---- leg 1: intra-engine mass migration under traffic -------------
    cfg = TensorEngineConfig(tick_interval=0.0, auto_fusion_ticks=0)
    engine = TensorEngine(config=cfg)
    engine.n_shards = 4  # logical shard blocks (no mesh required)
    oracle = TensorEngine(config=cfg)
    keys = np.arange(256, dtype=np.int64)
    total = np.zeros(256, dtype=np.int64)
    # warm wave: the pow2 gather/scatter kernels compile once here so
    # the measured storm pauses reflect the steady state
    engine.send_batch("ChaosLedger", "deposit", keys,
                      {"amount": np.zeros(256, np.int32)})
    engine.run_tick()
    oracle.send_batch("ChaosLedger", "deposit", keys,
                      {"amount": np.zeros(256, np.int32)})
    oracle.run_tick()
    engine.migrate_keys("ChaosLedger", keys[:8],
                        rng.integers(0, 4, 8))
    waves = 0
    for t in range(24):
        amounts = rng.integers(1, 100, 256).astype(np.int32)
        total += amounts
        for e in (engine, oracle):
            e.send_batch("ChaosLedger", "deposit", keys,
                         {"amount": amounts})
            e.run_tick()
        if t % 4 == 1:
            movers = rng.choice(keys, 48, replace=False)
            dst = rng.integers(0, 4, 48)
            t0 = _time.perf_counter()
            engine.migrate_keys("ChaosLedger", movers, dst)
            pauses.append(_time.perf_counter() - t0)
            waves += 1
    await engine.flush()
    await oracle.flush()
    mesh_report = check_mesh_single_activation(engine)
    got = _balances(engine, keys)
    want = _balances(oracle, keys)
    if not np.array_equal(got, want) \
            or not np.array_equal(got.astype(np.int64), total):
        raise InvariantViolation(
            "migration storm: migrated balances diverge from the "
            "never-migrated oracle")
    mesh_leg = {
        "waves": waves,
        "grains_migrated": int(engine.grains_migrated),
        "pins": len(engine.arenas["ChaosLedger"]._shard_override),
        "exact_vs_oracle": True,
        "mesh_single_activation": mesh_report["ok"],
    }

    # ---- leg 2: cluster storm (waves + join + drain) ------------------
    from orleans_tpu.testing.cluster import TestingCluster

    cluster = await TestingCluster(n_silos=2).start()
    cluster_leg: Dict[str, Any]
    try:
        ckeys = np.arange(1000, 1096, dtype=np.int64)
        ctotal = np.zeros(len(ckeys), dtype=np.int64)

        def residents(s):
            a = s.tensor_engine.arenas.get("ChaosLedger")
            return [] if a is None else \
                sorted(set(a.keys().tolist()) & set(ckeys.tolist()))

        async def drive(n: int) -> None:
            nonlocal ctotal
            for _ in range(n):
                amounts = rng.integers(1, 50, len(ckeys)).astype(np.int32)
                ctotal += amounts
                cluster.silos[0].tensor_engine.send_batch(
                    "ChaosLedger", "deposit", ckeys,
                    {"amount": amounts})
                await cluster.quiesce_engines()

        await drive(4)
        # warm cross-silo wave, then measured waves
        s0, s1 = cluster.silos[0], cluster.silos[1]
        warm = residents(s0)[:4]
        if warm:
            await s0.vector_router.migrate_keys_out(
                "ChaosLedger", np.asarray(warm, np.int64), s1.address)
        cross_moved = 0
        for _ in range(3):
            src, dst = (s0, s1) if rng.random() < 0.5 else (s1, s0)
            res = residents(src)
            if not res:
                continue
            movers = rng.choice(np.asarray(res, np.int64),
                                min(16, len(res)), replace=False)
            t0 = _time.perf_counter()
            cross_moved += await src.vector_router.migrate_keys_out(
                "ChaosLedger", movers, dst.address)
            pauses.append(_time.perf_counter() - t0)
            await drive(2)
        # JOIN mid-traffic: ring-change handoff pushes moved state
        s2 = await cluster.start_additional_silo()
        await cluster.wait_for_liveness_convergence()
        await drive(3)
        # DRAIN mid-traffic: the leaver migrates its residents out
        t0 = _time.perf_counter()
        await cluster.stop_silo(s1)
        pauses.append(_time.perf_counter() - t0)
        await drive(3)
        survivors = [s for s in cluster.silos if s is not s1]
        seen: Dict[int, int] = {}
        for s in survivors:
            for k in residents(s):
                seen[k] = seen.get(k, 0) + 1
        doubled = [k for k, n in seen.items() if n > 1]
        if doubled:
            raise InvariantViolation(
                f"migration storm: keys {doubled[:10]} live on "
                f"multiple silos after join+drain")
        if sorted(seen) != ckeys.tolist():
            raise InvariantViolation(
                f"migration storm: {len(ckeys) - len(seen)} keys "
                f"resident nowhere after join+drain")
        got = np.zeros(len(ckeys), dtype=np.int64)
        for s in survivors:
            a = s.tensor_engine.arenas.get("ChaosLedger")
            res = residents(s)
            if a is None or not res:
                continue
            rows, found = a.lookup_rows(np.asarray(res, np.int64))
            vals = np.asarray(a.state["balance"])[rows]
            idx = np.searchsorted(ckeys, np.asarray(res, np.int64))
            got[idx] = vals
        if not np.array_equal(got, ctotal):
            raise InvariantViolation(
                "migration storm: acknowledged deposits lost across "
                "cross-silo waves / join / drain")
        cluster_leg = {
            "cross_silo_grains": int(cross_moved),
            "join_adopted": len(residents(s2)),
            "zero_acknowledged_loss": True,
            "single_activation": True,
        }
    finally:
        await cluster.stop()

    worst_pause = max(pauses) if pauses else 0.0
    if worst_pause > pause_bound_s:
        raise InvariantViolation(
            f"migration storm: worst per-wave pause {worst_pause:.3f}s "
            f"exceeds the {pause_bound_s}s bound")
    return {
        "ok": True,
        "mesh_leg": mesh_leg,
        "cluster_leg": cluster_leg,
        "migration_waves": len(pauses),
        "worst_pause_s": round(worst_pause, 4),
        "pause_bound_s": pause_bound_s,
    }


async def fabric_midflush_scenario(seed: int,
                                   settle_bound_s: float = 10.0
                                   ) -> Dict[str, Any]:
    """Batched-fabric death smoke: the destination silo is HARD-KILLED
    mid-flush — with requests still parked in the sender's egress ring
    AND shipped direct calls whose batched results are still
    outstanding — and every frame member fails over NOW.  Ringed
    requests and stranded direct calls re-enter the per-message resend
    net as TRANSIENT, re-address onto the survivor, and settle well
    inside ``settle_bound_s`` (the anti-property: nobody waits out the
    response timeout on a dead silo's unanswered frame).  The
    kill→detection hop is the main plan's membership territory; here
    the oracle's ``on_silo_dead`` hook fires directly so the mid-flush
    timing is deterministic."""
    from orleans_tpu.chaos.invariants import InvariantViolation
    from orleans_tpu.runtime.messaging import Category, Direction, Message
    from orleans_tpu.runtime.runtime_client import CallbackData
    from orleans_tpu.testing.cluster import TestingCluster

    cluster = await TestingCluster(n_silos=2).start()
    try:
        s0, s1 = cluster.silos
        factory = s0.attach_client()
        # grains the hash placement hosts on the victim silo
        victims = []
        key = 77000
        while len(victims) < 8 and key < 77256:
            ref = factory.get_grain(IChaosKv, key)
            await ref.put(key)
            if cluster.find_silo_hosting(ref.grain_id) is s1:
                victims.append(ref)
            key += 1
        if len(victims) < 8:
            raise InvariantViolation(
                "fabric midflush: placement never landed 8 grains on "
                "the victim silo")
        before = s0.rpc_fabric.snapshot()
        await asyncio.gather(*(r.get() for r in victims))
        engaged = s0.rpc_fabric.snapshot()
        if engaged["calls_sent"] <= before["calls_sent"]:
            raise InvariantViolation(
                "fabric midflush: cross-silo calls never rode the "
                "fabric (scenario degenerate)")

        loop = asyncio.get_running_loop()
        rc = s0.runtime_client
        t0 = time.monotonic()
        # leg 1 — SHIPPED direct calls: slow_echo holds the victim long
        # enough that every batched result is still outstanding
        inflight = [asyncio.ensure_future(r.slow_echo(i))
                    for i, r in enumerate(victims)]
        for _ in range(8):
            await asyncio.sleep(0)  # let the invoke windows ship
        # leg 2 — RINGED requests: parked synchronously, with NO yield
        # between here and the kill (death arrives mid-flush)
        ringed = []
        for r in victims:
            msg = Message(category=Category.APPLICATION,
                          direction=Direction.REQUEST,
                          sending_silo=s0.address,
                          sending_grain=s0.client_grain_id,
                          target_silo=s1.address,
                          target_grain=r.grain_id,
                          method_name="get", args=())
            fut = loop.create_future()
            rc.callbacks[msg.id] = CallbackData(future=fut, message=msg)
            s0.message_center.send_message(msg)
            ringed.append(fut)
        parked = s0.rpc_fabric.pending()
        stranded = len(s0.rpc_fabric._direct)
        if parked == 0 or stranded == 0:
            raise InvariantViolation(
                f"fabric midflush: nothing mid-flush at the kill "
                f"(parked={parked} stranded={stranded})")
        cluster.kill_silo(s1)
        s0.on_silo_dead(s1.address)
        if s0.rpc_fabric.pending() != 0 or s0.rpc_fabric._direct:
            raise InvariantViolation(
                "fabric midflush: members survived fail_destination")
        done = await asyncio.wait_for(
            asyncio.gather(*inflight, *ringed, return_exceptions=True),
            settle_bound_s)
        settle_s = time.monotonic() - t0
        failures = [r for r in done if isinstance(r, BaseException)]
        if failures:
            raise InvariantViolation(
                f"fabric midflush: {len(failures)} members failed "
                f"instead of re-addressing ({failures[0]!r})")
        # re-addressed slow_echo calls land on the survivor and echo
        echoed = list(done[:len(inflight)])
        if echoed != list(range(len(inflight))):
            raise InvariantViolation(
                f"fabric midflush: re-addressed replies wrong: {echoed}")
        after = s0.rpc_fabric.snapshot()
        return {
            "ok": True,
            "parked_in_ring": parked,
            "stranded_direct": stranded,
            "bounced": after["bounced"] - before["bounced"],
            "settle_s": round(settle_s, 4),
            "settle_bound_s": settle_bound_s,
            "requests_resent": int(s0.metrics.requests_resent),
        }
    finally:
        await cluster.stop()


def smoke_plan(seed: int):
    """The canonical smoke scenario: finite pinned fault rules (fully
    deterministic trace signature), then partition → heal → hard-kill."""
    from orleans_tpu.chaos.plan import FaultPlan

    plan = FaultPlan(seed=seed)
    # storage flake: fail the first 2 writes through Default, then recover
    plan.rule("storage-flake", "storage", "fail", count=2,
              match=lambda ctx: ctx[0] == "Default")
    # membership CAS pressure: conflict 2 table writes (the oracle's CAS
    # retry loops absorb them)
    plan.rule("cas-conflict", "membership", "cas_conflict", count=2)
    # engine slab corruption: one NaN-poisoned injection
    plan.rule("nan-slab", "engine", "corrupt_nan", count=1,
              corrupt_fraction=0.1,
              match=lambda ctx: ctx == ("ChaosCounter", "poke"))
    # isolate silo1 long enough for the majority side to declare it dead
    # (a decisive split-brain outcome: silo1 sees its own DEAD row and
    # stops), heal, then hard-kill silo3 and let the survivor detect it
    plan.partition(0.2, [["silo1"], ["silo2", "silo3"]])
    plan.heal(1.8)
    plan.kill(2.4, "silo3")
    return plan


async def run_smoke(seed: int = 1234) -> Dict[str, Any]:
    """One full smoke run; returns the report dict (``ok`` = all nine
    invariants held).  Invariant violations are reported, not raised —
    the caller (CLI / bench step) decides the exit code."""
    import numpy as np

    from orleans_tpu.chaos.cluster import ChaosCluster
    from orleans_tpu.chaos.invariants import (
        InvariantViolation,
        check_arena_conservation,
        check_dead_letter_accounting,
        check_single_activation,
        check_membership_convergence,
        wait_for_at_least_once,
    )
    from orleans_tpu.streams import InMemoryQueueAdapter
    from orleans_tpu.streams.persistent import PersistentStreamProvider

    define_chaos_counter()
    t0 = time.monotonic()
    queue_backing = InMemoryQueueAdapter.shared_backing()

    def setup(silo):
        silo.add_stream_provider("pq", PersistentStreamProvider(
            InMemoryQueueAdapter(n_queues=4, backing=queue_backing),
            pull_period=0.01, consumer_cache_ttl=0.1))

    plan = smoke_plan(seed)
    cluster = await ChaosCluster(plan=plan, n_silos=3,
                                 silo_setup=setup).start()
    stream_key = int(time.time() * 1000) % (1 << 30)
    DELIVERED.pop(stream_key, None)
    invariants: Dict[str, Any] = {}
    try:
        await cluster.wait_for_liveness_convergence()
        factory = cluster.attach_client(0)

        # -- workload under fault pressure (before + through the plan) --
        kvs = [factory.get_grain(IChaosKv, i) for i in range(12)]
        await asyncio.gather(*(r.put(i) for i, r in enumerate(kvs)))
        # storage-flake fires here; saves must *surface* the failures,
        # not corrupt anything — retry each until the flake window passes
        flaked = 0
        for r in kvs[:4]:
            for _attempt in range(4):
                try:
                    await r.save()
                    break
                except Exception:
                    flaked += 1
                    await asyncio.sleep(0.01)

        produced = list(range(20))
        provider = cluster.silos[0].stream_provider("pq")
        stream = provider.get_stream("chaos-events", stream_key)
        await stream.on_next_batch(produced[:10])

        keys = np.arange(64, dtype=np.int64)
        engine0 = cluster.silos[0].tensor_engine
        engine0.send_batch("ChaosCounter", "poke", keys,
                           {"v": np.ones(64, np.float32)})  # nan-slab fires
        await cluster.quiesce_engines()

        # -- the scripted faults: partition → heal → hard-kill ----------
        await cluster.run_plan()

        # traffic AFTER the faults: the survivors must serve everything
        # (re-attach through a live silo — the original client silo may
        # be among the casualties)
        factory = cluster.live_silos()[0].attach_client()
        kvs = [factory.get_grain(IChaosKv, i) for i in range(12)]
        await asyncio.gather(*(r.put(100 + i)
                               for i, r in enumerate(kvs)))
        stream = cluster.live_silos()[0].stream_provider("pq") \
            .get_stream("chaos-events", stream_key)
        await stream.on_next_batch(produced[10:])
        # re-touch every vector key so rows lost with dead silos
        # re-activate on the survivors (population conservation is about
        # where keys LIVE, not about lossless state without a store)
        live_engine = cluster.live_silos()[0].tensor_engine
        live_engine.send_batch("ChaosCounter", "poke", keys,
                               {"v": np.zeros(64, np.float32)})

        # -- the nine invariants ----------------------------------------
        def _run(name, result):
            invariants[name] = result

        try:
            _run("membership_convergence",
                 await check_membership_convergence(cluster, timeout=10.0))
        except InvariantViolation as exc:
            _run("membership_convergence", {"ok": False, "error": str(exc)})
        try:
            _run("single_activation", check_single_activation(cluster))
        except InvariantViolation as exc:
            _run("single_activation", {"ok": False, "error": str(exc)})
        try:
            _run("arena_conservation",
                 await check_arena_conservation(cluster, "ChaosCounter",
                                                keys))
        except InvariantViolation as exc:
            _run("arena_conservation", {"ok": False, "error": str(exc)})
        try:
            _run("stream_at_least_once",
                 await wait_for_at_least_once(
                     produced,
                     lambda: list(DELIVERED.get(stream_key, [])),
                     timeout=15.0))
        except InvariantViolation as exc:
            _run("stream_at_least_once", {"ok": False, "error": str(exc)})
        try:
            _run("dead_letter_accounting",
                 check_dead_letter_accounting(cluster))
        except InvariantViolation as exc:
            _run("dead_letter_accounting", {"ok": False, "error": str(exc)})
        # the durable state plane's kill-mid-traffic scenario (seeded,
        # engine-level: the cluster above has no snapshot store — the
        # durability contract is an engine property, checked against a
        # fresh engine recovering over the same durable backing)
        try:
            _run("durability_accounting",
                 await durability_kill_scenario(seed))
        except (InvariantViolation, AssertionError) as exc:
            _run("durability_accounting", {"ok": False, "error": str(exc)})
        # the closed-loop rebalance plane's storm (seeded, its own
        # engines + cluster — mass migration at both granularities
        # under traffic, plus join + drain, beside the durability kill)
        try:
            _run("migration_storm",
                 await migration_storm_scenario(seed))
        except (InvariantViolation, AssertionError) as exc:
            _run("migration_storm", {"ok": False, "error": str(exc)})
        # warm-standby failover (seeded, engine-level like the kill
        # scenario): log shipping while traffic runs, hard kill,
        # promotion fence + tail fold-replay, zero acknowledged loss
        try:
            _run("standby_failover",
                 await standby_failover_scenario(seed))
        except (InvariantViolation, AssertionError) as exc:
            _run("standby_failover", {"ok": False, "error": str(exc)})
        # the batched silo→silo fabric's death contract (seeded, its
        # own 2-silo cluster): a destination killed MID-FLUSH fails
        # every frame member immediately — ringed and shipped alike —
        # and the members re-address instead of stranding
        try:
            _run("fabric_midflush_failfast",
                 await fabric_midflush_scenario(seed))
        except (InvariantViolation, AssertionError) as exc:
            _run("fabric_midflush_failfast",
                 {"ok": False, "error": str(exc)})

        # flight-recorder evidence: every silo's ring (dead silos too —
        # their in-memory spans ARE the crash evidence), correlated by
        # trace id against the fault trace so an injected fault maps to
        # the exact request it hit
        flight = cluster.flight_recorder_dump("chaos smoke")
        trace_correlation = correlate_faults_with_spans(
            cluster.trace.to_list(), flight)
    finally:
        await cluster.stop()

    ok = all(v.get("ok") for v in invariants.values()) \
        and len(invariants) == 9
    return {
        "metric": "chaos_smoke",
        "ok": ok,
        "seed": seed,
        "elapsed_s": round(time.monotonic() - t0, 2),
        "plan": plan.describe(),
        "invariants": invariants,
        "storage_flakes_surfaced": flaked,
        "fault_trace": cluster.trace.to_list(),
        "trace_signature": [list(s) for s in cluster.trace.signature()],
        "interposer": cluster.interposer.snapshot(),
        "flight_recorder": flight,
        # the tracing-plane acceptance evidence: ≥1 injected fault's
        # FaultTrace entry shares a trace_id with the spans of the
        # request it affected
        "trace_correlation": trace_correlation,
    }


def correlate_faults_with_spans(fault_events: List[Dict[str, Any]],
                                flight: Dict[str, Dict[str, Any]]
                                ) -> Dict[str, Any]:
    """Cross-reference FaultTrace entries' trace_id tags with the trace
    ids present in the flight-recorder dumps: the injected-fault ↔
    affected-request mapping the tracing plane exists to provide."""
    fault_tids = {str(e["detail"].get("trace_id")) for e in fault_events}
    fault_tids -= {"None", ""}
    span_tids: set = set()
    for dump in flight.values():
        # normalize to strings: trace ids are ints in-memory but reach
        # the FaultTrace detail str()-ed (FaultTrace.to_list)
        span_tids.update(str(k) for k in dump.get("traces", {}))
    shared = sorted(fault_tids & span_tids)
    return {"ok": bool(shared),
            "shared_trace_ids": shared[:8],
            "fault_trace_ids": len(fault_tids),
            "span_trace_ids": len(span_tids)}


def run_chaos_smoke(seed: int = 1234, repeat: int = 1) -> Dict[str, Any]:
    """Run the smoke ``repeat`` times (fresh cluster + loop each) and
    fold into one report; with repeat > 1 the trace signatures must be
    identical across runs — the (seed, plan) replayability contract."""
    runs = [asyncio.run(run_smoke(seed)) for _ in range(repeat)]
    # surface the first FAILING run's evidence (invariants + trace), not
    # blindly run 1's — ok=false with all-green evidence is undebuggable
    primary = next((r for r in runs if not r["ok"]), runs[0])
    report = dict(primary)
    if repeat > 1:
        sigs = [r["trace_signature"] for r in runs]
        reproducible = all(s == sigs[0] for s in sigs)
        report["runs"] = repeat
        report["reproducible"] = reproducible
        report["run_results"] = [
            {"ok": r["ok"],
             "invariants": {k: v.get("ok")
                            for k, v in r["invariants"].items()}}
            for r in runs]
        report["ok"] = reproducible and all(r["ok"] for r in runs)
    return report


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m orleans_tpu.chaos",
        description="run the seeded chaos smoke plan and emit a JSON "
                    "fault/invariant report")
    parser.add_argument("--seed", type=int, default=1234)
    parser.add_argument("--out", default="CHAOS_SMOKE.json",
                        help="report path ('-' = stdout only)")
    parser.add_argument("--repeat", type=int, default=1,
                        help="run the plan N times and assert identical "
                             "trace signatures (reproducibility proof)")
    args = parser.parse_args(argv)

    report = run_chaos_smoke(seed=args.seed, repeat=args.repeat)
    print(json.dumps(report))
    if args.out != "-":
        with open(args.out, "w") as f:
            f.write(json.dumps(report, indent=1) + "\n")
    return 0 if report["ok"] else 1
