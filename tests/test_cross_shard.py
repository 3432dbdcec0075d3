"""Device-resident cross-shard routing (tensor/exchange.py).

Runs on the conftest-forced 8-device virtual CPU mesh and exercises the
REAL exchange path: bucket-by-destination-shard + lax.all_to_all inside
the compiled program, overflow redelivery with original inject stamps,
the fused-window threading, and the directory/arena agreement the whole
design rests on ("the directory IS the sharding map").
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from orleans_tpu.tensor import TensorEngine
from orleans_tpu.tensor.arena import shard_of_keys
from orleans_tpu.tensor.exchange import (
    exchangeable_args,
    ladder_ceil,
    pow2ceil,
)

from samples.routing import (
    SINK_BASE,
    RouteSink,     # noqa: F401 — registers the vector grains
    RouteSource,   # noqa: F401
    build_ratio_destinations,
    run_routing_load,
)

N_DEV = 8


def _mesh(n: int = N_DEV) -> Mesh:
    devices = jax.devices("cpu")
    assert len(devices) >= n, "conftest must force 8 host devices"
    return Mesh(np.array(devices[:n]), ("grains",))


def _engine(**kw) -> TensorEngine:
    e = TensorEngine(mesh=_mesh(), **kw)
    e.config.auto_fusion_ticks = 0  # tests opt in explicitly
    # the virtual CPU mesh disengages the structured path by default
    # (identity mode — config.exchange_structured "auto"); these suites
    # exist to prove the STRUCTURED machinery, so they pin it on
    e.config.exchange_structured = "always"
    return e


def _sink_state(engine, n_sinks: int):
    arena = engine.arena_for("RouteSink")
    sinks = np.arange(SINK_BASE, SINK_BASE + n_sinks, dtype=np.int64)
    rows, found = arena.lookup_rows(sinks)
    assert found.all()
    return (np.asarray(arena.state["total"])[rows],
            np.asarray(arena.state["received"])[rows])


# ---------------------------------------------------------------------------
# exchange kernel unit level
# ---------------------------------------------------------------------------

def test_exchange_delivery_set_and_locality():
    """The exchange preserves the (row, payload) delivery multiset
    exactly (minus counted drops) and every received lane's row belongs
    to the shard block of the position it landed in."""
    engine = _engine(initial_capacity=16 * N_DEV)
    arena = engine.arena_for("RouteSink")
    arena.resolve_rows(np.arange(SINK_BASE, SINK_BASE + 100,
                                 dtype=np.int64))
    cap = arena.capacity
    rng = np.random.default_rng(0)
    m = 100
    rows = rng.integers(0, cap, m).astype(np.int32)
    mask = np.ones(m, bool)
    mask[::7] = False
    v = rng.integers(1, 9, m).astype(np.float32)
    r2, a2, m2, dropped, stats = engine.exchange.dispatch(
        arena, jnp.asarray(rows), {"v": jnp.asarray(v),
                                   "t": np.float32(3.0)},
        jnp.asarray(mask))
    r2h, vh, m2h, dh, sh = map(np.asarray, (r2, a2["v"], m2, dropped,
                                            stats))
    valid_in = mask & (rows >= 0)
    assert int(sh[2]) == int(valid_in.sum()) - int(dh.sum())
    sent = collections.Counter(
        zip(rows[valid_in & ~dh].tolist(),
            v[valid_in & ~dh].tolist()))
    got = collections.Counter(zip(r2h[m2h].tolist(), vh[m2h].tolist()))
    assert sent == got
    # locality: the received lane's row lives in the block of the shard
    # that received it — the step kernel's scatter is shard-local
    per_shard = len(r2h) // N_DEV
    pos_shard = np.arange(len(r2h)) // per_shard
    assert ((r2h[m2h] // arena.shard_capacity) == pos_shard[m2h]).all()
    # scalar leaves bypass the exchange untouched
    assert a2["t"] == np.float32(3.0)


def test_exchange_plan_ladder_and_clamp():
    """Plan contract: widths the plane itself produced (exchange
    outputs, aligned layouts — registered transport widths) keep their
    exact per-shard split (re-quantizing would shift lanes out of
    their home chunks); everything else — including organic batches
    that merely happen to be n-divisible — quantizes onto the {2^k} ∪
    {3·2^(k-1)} ladder, so the compile set stays O(log) under drifting
    population.  An unmeasured site falls back to the worst-case cap
    formula; a measured site uses its quantized grant.  (Host-aligned
    batches never reach plan(): the fused build skips their exchange
    entirely.)"""
    engine = _engine(initial_capacity=16 * N_DEV)
    xch = engine.exchange
    for m in (1, 100, 4096, 100_000):
        L, cap = xch.plan(m)
        assert L == ladder_ceil(-(-m // N_DEV)) >= -(-m // N_DEV)
        # fallback (unmeasured): worst-case formula, clamped to L
        assert cap == pow2ceil(cap) and cap <= L
        assert cap >= min(L, engine.config.exchange_pad_quantum)
    # a registered transport width (n·544 is no ladder rung) keeps its
    # exact split; the same width unregistered would re-quantize
    assert xch.plan(8 * 544)[0] == ladder_ceil(544) != 544
    xch.note_transport_width(8 * 544)
    assert xch.plan(8 * 544)[0] == 544
    # a measured site uses its ladder-quantized grant (headroom 1.5
    # over the observed per-destination peak), clamped to L
    site = ("RouteSink", "recv")
    xch.observe_need(site, np.array([40, 3, 0, 0, 0, 0, 0, 0]),
                     valid=4096, width=4096)
    want = ladder_ceil(int(np.ceil(40 * engine.config.exchange_headroom)))
    assert xch.plan(4096, site=site) == (512, want)
    assert xch.plan(8, site=site) == (1, 1)  # clamp: cap ≤ L
    # the same share of a batch twice as wide: twice the grant
    assert xch.plan(8192, site=site) == (1024, 2 * want)
    # zero demand quantizes to cap 0 — the classification-only fast path
    site0 = ("RouteSink", "quiet")
    xch.observe_need(site0, np.zeros(N_DEV, np.int64), valid=4096,
                     width=4096)
    assert xch.plan(4096, site=site0) == (512, 0)
    # the occupancy toggle is live: off → every site uses the fallback
    engine.config.exchange_occupancy_sizing = False
    L, cap = xch.plan(4096, site=site)
    assert cap >= min(L, engine.config.exchange_pad_quantum)


def test_slab_style_args_are_not_exchangeable():
    """Handlers consuming a whole buffer per tick (leaf leading dim !=
    lane count — the twitter dispatcher shape) must keep the legacy
    path: permuting rows away from the buffer would corrupt them."""
    assert exchangeable_args({"v": np.zeros(8), "s": np.float32(1)}, 8)
    assert not exchangeable_args({"slab": np.zeros(64)}, 8)


# ---------------------------------------------------------------------------
# engine integration: exactness across the ratio sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ratio", [0.0, 0.5, 0.9])
def test_routing_exact_vs_exchange_off(run, ratio):
    """Exchange ON must produce bit-identical sink state to the
    implicit-collective baseline at every cross-shard ratio (integer
    payloads through seg_sum: no float-order escape hatch)."""

    async def main():
        e_on = _engine(initial_capacity=1024)
        st_on = await run_routing_load(e_on, 512, 256, ratio, n_ticks=4)
        e_off = _engine(initial_capacity=1024)
        e_off.config.cross_shard_exchange = False
        st_off = await run_routing_load(e_off, 512, 256, ratio,
                                        n_ticks=4)
        assert st_on["total_ticks"] == st_off["total_ticks"]
        t_on, r_on = _sink_state(e_on, 256)
        t_off, r_off = _sink_state(e_off, 256)
        np.testing.assert_array_equal(t_on, t_off)
        np.testing.assert_array_equal(r_on, r_off)
        assert r_on.sum() == 512 * st_on["total_ticks"]
        xs = e_on.snapshot()["exchange"]
        assert xs["exchanges_run"] > 0 and xs["dropped_msgs"] == 0
        assert e_off.snapshot()["exchange"]["exchanges_run"] == 0
        if ratio > 0:
            assert xs["cross_shard_msgs"] > 0

    run(main())


def test_cross_shard_count_matches_constructed_ratio(run):
    """The stats the exchange reports reconcile with the analytically
    constructed traffic: sink deliveries cross shards exactly at the
    requested ratio (sources land on their own shard post-exchange, so
    the delivery leg's crossings are ratio * lanes per tick)."""

    async def main():
        n_src, n_sink, ratio, ticks = 512, 256, 0.5, 4
        e = _engine(initial_capacity=1024)
        await run_routing_load(e, n_src, n_sink, ratio, n_ticks=ticks,
                               warm_ticks=0)
        xs = e.snapshot()["exchange"]
        # two exchanged legs per tick: the source injection (whose
        # crossings depend on the injection layout) and the sink
        # delivery (whose crossings are EXACTLY the constructed ratio —
        # post-exchange, every emit lane sits on its source's home
        # shard).  The total is source-leg + ratio * lanes per tick.
        src = np.arange(n_src, dtype=np.int64)
        rows, _ = e.arena_for("RouteSource").lookup_rows(src)
        lane_shard = np.arange(n_src) // -(-n_src // N_DEV)
        src_cross = int((shard_of_keys(src, N_DEV) != lane_shard).sum())
        sink_cross = int(round(ratio * n_src))
        assert xs["cross_shard_msgs"] == (src_cross + sink_cross) * ticks
        assert xs["delivered_msgs"] == 2 * n_src * ticks
        assert xs["dropped_msgs"] == 0

    run(main())


# ---------------------------------------------------------------------------
# overflow redelivery + latency-ledger stamps
# ---------------------------------------------------------------------------

def test_overflow_redelivers_exactly_with_original_stamp(run):
    """Max-skew traffic (every message to ONE sink) with a deliberately
    tiny bucket: lanes overflow, redeliver over later ticks, and nothing
    is lost — and the device latency ledger records the redelivered
    lanes with their ORIGINAL inject stamp (nonzero tick deltas)."""

    async def main():
        e = _engine(initial_capacity=1024)
        e.config.exchange_pad_quantum = 2
        e.config.exchange_capacity_factor = 0.25
        src = np.arange(256, dtype=np.int64)
        e.arena_for("RouteSource").reserve(256)
        e.arena_for("RouteSink").reserve(64)
        e.arena_for("RouteSource").resolve_rows(src)
        e.arena_for("RouteSink").resolve_rows(
            np.arange(64, dtype=np.int64))
        inj = e.make_injector("RouteSource", "send", src)
        dst = jnp.asarray(np.zeros(256, np.int32))
        v = jnp.asarray(np.ones(256, np.float32))
        for t in range(3):
            inj.inject({"dst": dst, "v": v, "tick": np.int32(t)})
            await e.drain_queues()
        await e.flush()
        xs = e.snapshot()["exchange"]
        assert xs["dropped_msgs"] > 0 and xs["redeliveries"] > 0
        row = e.arena_for("RouteSink").read_row(0)
        assert int(row["received"]) == 256 * 3  # nothing lost
        led = e.ledger.snapshot()
        sink = led["RouteSink.recv"]
        assert sink["total"] == 256 * 3  # counted once each
        # redelivered lanes completed ticks after their stamp: buckets
        # beyond "same tick" must be populated
        assert sum(sink["counts"][1:]) > 0, sink

    run(main())


def test_checkpoint_defers_while_exchange_checks_parked(run):
    """Review-fix regression: a periodic checkpoint with exchange
    overflow redeliveries still parked would persist subscriber effects
    without their source update — the write defers one tick (the checks
    drain and requeue) and lands after the redeliveries apply."""
    from orleans_tpu.tensor import MemoryVectorStore
    from orleans_tpu.tensor.engine import _ExchangeCheck

    async def main():
        e = TensorEngine(mesh=_mesh(), initial_capacity=64,
                         store=MemoryVectorStore())
        e.config.auto_fusion_ticks = 0
        e.config.checkpoint_every_ticks = 1
        arena = e.arena_for("RouteSink")
        arena.resolve_rows(np.arange(SINK_BASE, SINK_BASE + 8,
                                     dtype=np.int64))
        e.tick_number = 5
        keys = jnp.asarray(
            np.arange(SINK_BASE, SINK_BASE + 4).astype(np.int32))
        e._exchange_checks.append(_ExchangeCheck(
            type_name="RouteSink", method="recv", keys=keys,
            args={"v": jnp.ones(4, jnp.float32),
                  "count": jnp.ones(4, jnp.int32)},
            dropped=jnp.asarray(np.array([True, False, False, False])),
            stats=jnp.asarray(np.array([1, 1, 3], np.int32)),
            width=4, inject_tick=2))
        assert e.maybe_periodic_checkpoint() == 0.0  # deferred
        assert not e._exchange_checks                # drained…
        redelivery = e.queues[("RouteSink", "recv")]
        assert redelivery and redelivery[0].inject_tick == 2  # …requeued
        await e.flush()  # redelivery applies (ticks checkpoint en route)
        assert e._last_checkpoint_tick > 0

    run(main())


def test_host_batch_not_misattributed_cross_shard(run):
    """Review-fix regression: a host-key batch for a method previously
    seen only through the exchange is organic traffic (host batches
    never exchange by design) — not a cross_shard toggle event."""

    async def main():
        e = _engine(initial_capacity=1024)
        await run_routing_load(e, 256, 128, 0.5, n_ticks=2,
                               warm_ticks=0)
        before = e.compile_tracker.by_cause.get("cross_shard", 0)
        e.send_batch("RouteSink", "recv",
                     np.arange(SINK_BASE, SINK_BASE + 16,
                               dtype=np.int64),
                     {"v": np.ones(16, np.float32),
                      "count": np.ones(16, np.int32)})
        await e.flush()
        assert e.compile_tracker.by_cause.get("cross_shard", 0) == before

    run(main())


def test_exchange_accounting_invariant(run):
    """The chaos-plane checker: parked checks drained at quiescence and
    counters internally consistent."""
    from orleans_tpu.chaos.invariants import check_exchange_accounting

    async def main():
        e = _engine(initial_capacity=1024)
        await run_routing_load(e, 256, 128, 0.5, n_ticks=3)
        report = check_exchange_accounting(e)
        assert report["ok"] and report["delivered_msgs"] > 0

    run(main())


# ---------------------------------------------------------------------------
# fused windows + autofuse
# ---------------------------------------------------------------------------

def test_fused_window_exchange_exact(run):
    """The exchange threads through the fused lax.scan: a fused run over
    the mesh matches the unfused exchange-off baseline exactly."""

    async def main():
        e_f = _engine(initial_capacity=1024)
        st_f = await run_routing_load(e_f, 512, 256, 0.5, n_ticks=4,
                                      fused_window=2)
        e_off = _engine(initial_capacity=1024)
        e_off.config.cross_shard_exchange = False
        st_o = await run_routing_load(e_off, 512, 256, 0.5, n_ticks=4,
                                      warm_ticks=2)
        t_f, r_f = _sink_state(e_f, 256)
        t_o, r_o = _sink_state(e_off, 256)
        # warm schedules differ (the fused path re-plans its bucket
        # caps across two warm windows), so per-tick state compares by
        # cross-multiplication — integer payloads, exact
        tf, to = st_f["total_ticks"], st_o["total_ticks"]
        np.testing.assert_array_equal(t_f * to, t_o * tf)
        np.testing.assert_array_equal(r_f * to, r_o * tf)

    run(main())


def test_fused_exchange_toggle_retraces_with_cause(run):
    """A live cross_shard_exchange toggle re-traces the fused program
    (cause config_toggle) instead of silently running the stale plan."""

    async def main():
        import jax.numpy as jnp

        e = _engine(initial_capacity=1024)
        src = np.arange(128, dtype=np.int64)
        e.arena_for("RouteSource").resolve_rows(src)
        e.arena_for("RouteSink").resolve_rows(
            np.arange(SINK_BASE, SINK_BASE + 64, dtype=np.int64))
        dst = build_ratio_destinations(
            src, np.arange(SINK_BASE, SINK_BASE + 64, dtype=np.int64),
            N_DEV, 0.5, seed=0)
        prog = e.fuse_ticks("RouteSource", "send", src)
        static = {"dst": jnp.asarray(dst.astype(np.int32)),
                  "v": jnp.ones(128, jnp.float32)}
        prog.run({"tick": jnp.arange(2, dtype=jnp.int32)},
                 static_args=static)
        assert prog.verify() == 0
        assert prog._exchange_on is True
        before = e.compile_tracker.by_cause.get("config_toggle", 0)
        e.config.cross_shard_exchange = False
        prog.run({"tick": jnp.arange(2, dtype=jnp.int32)},
                 static_args=static)
        assert prog.verify() == 0
        assert prog._exchange_on is False
        assert e.compile_tracker.by_cause["config_toggle"] == before + 1

    run(main())


def test_autofuse_engages_over_exchange(run):
    """Transparent auto-fusion on the mesh: the steady routing pattern
    engages, runs exchanged windows, and stays exact."""

    async def main():
        e = _engine(initial_capacity=1024)
        e.config.auto_fusion_ticks = 3
        e.config.auto_fusion_window = 4
        stats = await run_routing_load(e, 256, 128, 0.5, n_ticks=16,
                                       warm_ticks=0)
        assert e.autofuser.ticks_fused > 0, stats
        assert e.autofuser.windows_rolled_back == 0
        _t, received = _sink_state(e, 128)
        assert received.sum() == 256 * 16

    run(main())


# ---------------------------------------------------------------------------
# compile-cause + phase accounting
# ---------------------------------------------------------------------------

def test_live_toggle_records_cross_shard_cause(run):
    """Flipping the exchange re-specializes a seen (type, method, m)
    step — attributed as cause 'cross_shard', not organic shape churn."""

    async def main():
        e = _engine(initial_capacity=1024)
        e.config.cross_shard_exchange = False
        await run_routing_load(e, 256, 128, 0.5, n_ticks=2,
                               warm_ticks=0)
        assert e.compile_tracker.by_cause.get("cross_shard", 0) == 0
        e.config.cross_shard_exchange = True
        await run_routing_load(e, 256, 128, 0.5, n_ticks=2,
                               warm_ticks=0)
        assert e.compile_tracker.by_cause["cross_shard"] > 0

    run(main())


def test_exchange_phase_reconciles(run):
    """The exchange is its own tick phase; phase sums still reconcile
    with tick wall time (no double-counted stage)."""

    async def main():
        e = _engine(initial_capacity=1024)
        await run_routing_load(e, 256, 128, 0.5, n_ticks=4)
        prof = e.profiler
        assert prof.phase_seconds["exchange"] > 0.0
        assert prof.overrun_ticks == 0
        snap = prof.snapshot()
        assert "exchange" in snap["phase_seconds"]

    run(main())


# ---------------------------------------------------------------------------
# satellite: directory/arena agreement property test
# ---------------------------------------------------------------------------

def test_directory_arena_shard_agreement(run):
    """THE sharding-map claim, enforced: for random keys, the ring's
    device-granularity helper, the arena's row-block placement, and the
    exchange's rows//shard_capacity bucketing all agree — across
    growth (repack) and a mesh reshard."""
    from orleans_tpu.runtime.ring import device_shard_of_keys

    async def main():
        rng = np.random.default_rng(7)
        e = _engine(initial_capacity=2 * N_DEV)  # tiny: forces growth
        arena = e.arena_for("RouteSink")
        keys = np.unique(rng.integers(0, 2**31 - 2, 500,
                                      dtype=np.int64))

        def check(n_shards: int) -> None:
            rows, found = arena.lookup_rows(keys)
            assert found.all()
            got = rows // arena.shard_capacity
            want = shard_of_keys(keys, n_shards)
            np.testing.assert_array_equal(got, want)
            np.testing.assert_array_equal(
                want, device_shard_of_keys(keys, n_shards))

        arena.resolve_rows(keys[:50])   # initial block
        arena.resolve_rows(keys)        # forces several growths
        check(N_DEV)
        # growth again after more activations
        more = np.unique(rng.integers(2**20, 2**31 - 2, 1000,
                                      dtype=np.int64))
        arena.resolve_rows(more)
        check(N_DEV)
        # mesh reshard 8 → 4: same function at the new granularity
        await e.reshard(_mesh(4))
        check(4)

    run(main())


# ---------------------------------------------------------------------------
# satellite: chaos — mesh reshard mid-traffic × eviction epochs
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_mesh_reshard_mid_traffic(run):
    """The chaos scenario the issue names: reshard the mesh 8→4→8 while
    routing traffic flows, evict idle sinks mid-run (eviction epochs ×
    exchange), and assert the mesh invariants — single activation,
    home-block placement, exchange accounting, and exact end-to-end
    conservation (no message lost or doubled)."""
    from orleans_tpu.chaos.invariants import (
        check_exchange_accounting,
        check_mesh_single_activation,
    )
    from orleans_tpu.tensor import MemoryVectorStore

    async def main():
        store = MemoryVectorStore()
        e = TensorEngine(mesh=_mesh(), initial_capacity=1024,
                         store=store)
        e.config.auto_fusion_ticks = 0
        e.config.exchange_structured = "always"  # exercise the machinery
        n_src, n_sink = 256, 128
        src = np.arange(n_src, dtype=np.int64)
        sinks = np.arange(SINK_BASE, SINK_BASE + n_sink, dtype=np.int64)
        dst = build_ratio_destinations(src, sinks, N_DEV, 0.5, seed=3)
        e.arena_for("RouteSource").resolve_rows(src)
        e.arena_for("RouteSink").resolve_rows(sinks)
        inj = e.make_injector("RouteSource", "send", src)
        dst_d = jnp.asarray(dst.astype(np.int32))
        v = jnp.asarray(np.ones(n_src, np.float32))
        ticks = 0

        async def burst(n: int) -> None:
            nonlocal ticks
            for _ in range(n):
                inj.inject({"dst": dst_d, "v": v,
                            "tick": np.int32(ticks)})
                await e.drain_queues()
                ticks += 1

        await burst(3)
        await e.reshard(_mesh(4))          # mid-traffic shrink
        inj = e.make_injector("RouteSource", "send", src)
        await burst(3)
        # eviction epoch churn: evict EVERYTHING idle (write-back to the
        # store), then keep routing — sinks re-activate from storage
        await e.flush()
        evicted = e.collect_idle(max_idle_ticks=0)
        assert evicted > 0
        await burst(3)
        await e.reshard(_mesh(N_DEV))      # grow back
        inj = e.make_injector("RouteSource", "send", src)
        await burst(3)
        await e.flush()

        check_mesh_single_activation(e)
        check_exchange_accounting(e)
        # sinks with no post-eviction traffic live only in the store —
        # re-activation loads their state back (Catalog stage-2 analog)
        e.arena_for("RouteSink").resolve_rows(sinks)
        check_mesh_single_activation(e)
        _total, received = _sink_state(e, n_sink)
        assert received.sum() == n_src * 12  # every tick, exactly once

    run(main())


# ---------------------------------------------------------------------------
# satellite: metrics + dashboard plumbing
# ---------------------------------------------------------------------------

def test_route_metrics_declared_and_dashboard_row():
    from orleans_tpu.dashboard import render_text, view_from_snapshots
    from orleans_tpu.metrics import CATALOG, MetricsRegistry

    for name in ("route.cross_shard_msgs", "route.delivered_msgs",
                 "route.exchange_dropped", "route.exchanges",
                 "route.exchange_s", "route.exchange_util",
                 "route.exchange_overlap_s", "route.exchange_cap",
                 "arena.shard_occupancy"):
        assert name in CATALOG, name
    reg = MetricsRegistry(source="s1")
    reg.apply("route.cross_shard_msgs", 100.0, None)
    reg.apply("route.delivered_msgs", 150.0, None)
    reg.apply("route.exchanges", 4.0, None)
    reg.apply("route.exchange_dropped", 2.0, None)
    reg.apply("route.exchange_s", 0.5, None)
    reg.apply("route.exchange_overlap_s", 0.25, None)
    reg.gauge("route.exchange_util").set(0.75)
    reg.gauge("route.exchange_cap", {"shard": "3"}).set(96.0)
    view = view_from_snapshots([reg.snapshot()])
    xs = view["cluster"]["cross_shard"]
    assert xs["exchanged_messages"] == 100
    assert xs["delivered_messages"] == 150
    assert xs["dropped_redelivered"] == 2
    # utilization + overlap + occupancy caps ride the row (the
    # occupancy-sizing satellite contract)
    assert xs["bucket_utilization"] == 0.75
    assert xs["overlap_seconds"] == 0.25
    assert xs["caps"] == {"3": 96.0}
    text = render_text(view)
    assert "cross-shard (on device)" in text
    assert "util 0.75" in text


def test_shard_occupancy_gauge(run):
    async def main():
        e = _engine(initial_capacity=16 * N_DEV)
        arena = e.arena_for("RouteSink")
        arena.resolve_rows(np.arange(200, dtype=np.int64))
        occ = arena.shard_occupancy()
        assert occ.sum() == 200 and len(occ) == N_DEV
        expected = np.bincount(shard_of_keys(
            np.arange(200, dtype=np.int64), N_DEV), minlength=N_DEV)
        np.testing.assert_array_equal(occ, expected)

    run(main())


# ---------------------------------------------------------------------------
# occupancy-sized caps: estimator, churn property, re-quantization cause
# ---------------------------------------------------------------------------

def test_estimator_grows_immediately_shrinks_with_patience():
    """Cap grants move on the quantized ladder: up the moment demand
    overflows (undersized caps cost a redelivery EVERY tick), down only
    after exchange_shrink_patience calm drains (a noisy steady state
    must not flap compiles)."""
    engine = _engine(initial_capacity=16 * N_DEV)
    xch = engine.exchange
    engine.config.exchange_headroom = 1.5
    engine.config.exchange_shrink_patience = 3
    site = ("RouteSink", "recv")
    v0 = xch.cap_version
    M = N_DEV * 512  # the batch every observation here is read on
    # first observation grants immediately
    xch.observe_need(site, np.array([20] + [0] * (N_DEV - 1)),
                     valid=M, width=M)
    g1 = xch.grant_for(site, M)
    assert g1 == ladder_ceil(int(np.ceil(20 * 1.5)))
    assert xch.cap_version == v0 + 1
    # growth is immediate
    xch.observe_need(site, np.array([200] + [0] * (N_DEV - 1)),
                     valid=M, width=M)
    g2 = xch.grant_for(site, M)
    assert g2 == ladder_ceil(int(np.ceil(200 * 1.5))) > g1
    assert xch.cap_version == v0 + 2
    # calm traffic: no shrink before patience drains
    for i in range(2):
        xch.observe_need(site, np.array([10] + [0] * (N_DEV - 1)),
                         valid=M, width=M)
        assert xch.grant_for(site, M) == g2, f"shrank after {i + 1} obs"
    # the patience-th calm drain shrinks to the windowed peak
    xch.observe_need(site, np.array([10] + [0] * (N_DEV - 1)),
                     valid=M, width=M)
    g3 = xch.grant_for(site, M)
    assert g3 == ladder_ceil(int(np.ceil(10 * 1.5)))
    assert xch.cap_version == v0 + 3
    # per-shard cap gauges quantize the all-time peak per destination
    caps = xch.cap_gauges()
    assert caps[0] == ladder_ceil(int(np.ceil(200 * 1.5)))
    assert caps[1] == 0
    # a calm window whose rung is above half the grant does not shrink
    # it: demand at a rung boundary must not flap the plan
    xch.observe_need(site, np.array([120] + [0] * (N_DEV - 1)),
                     valid=M, width=M)
    g4 = xch.grant_for(site, M)
    for _ in range(3):
        xch.observe_need(site, np.array([70] + [0] * (N_DEV - 1)),
                         valid=M, width=M)
    assert xch.grant_for(site, M) == g4 > g3
    assert xch.cap_version == v0 + 4
    # the same demand out of half as many valid lanes is twice the share
    wide = xch.grant_for(site, 2 * M)
    xch.observe_need(site, np.array([120] + [0] * (N_DEV - 1)),
                     valid=M // 2, width=M)
    assert xch.grant_for(site, 2 * M) > wide


def test_estimate_holds_across_batch_widths():
    """A site whose ticks merge a varying number of slabs sees batches
    of many widths.  Its estimate is a share of the valid lanes, so the
    plan for every width is set by the first observation and never
    moves after it: no exchange program compiles once each width has
    run.  Even traffic puts every destination's share (1/n, times the
    1.5 headroom) on a rung boundary, one destination a little below
    it: noise must not move the plan."""
    engine = _engine(initial_capacity=16 * N_DEV)
    xch = engine.exchange
    site = ("RouteSink", "recv")
    rng = np.random.default_rng(25)
    slab = 16384 * N_DEV  # slices as wide as a chip's share of a slab
    widths = [k * slab for k in range(1, 9)]

    # destination shards by key hash hold a little more or less than
    # 1/n of the targets: one here holds 5% less
    share = np.full(N_DEV, 1.0)
    share[3] = 0.95
    share /= share.sum()

    def observe(m):
        # each source slice's lanes, bound for the destination shards
        per_src = rng.multinomial(m // N_DEV, share, size=N_DEV)
        np.fill_diagonal(per_src, 0)  # a slice's own lanes stay home
        xch.observe_need(site, per_src.max(axis=0), per_src.sum(axis=0),
                         valid=m, width=m)

    observe(widths[0])
    plans = {m: xch.plan_ex(m, site=site) for m in widths}
    version = xch.cap_version
    for m in rng.choice(widths, size=200):
        observe(int(m))
    for m in widths:
        mode, L, cap, pd = xch.plan_ex(m, site=site)
        want = plans[m]
        assert (mode, L, cap) == want[:3], m
        assert (pd is None) == (want[3] is None), m
        if pd is not None:
            assert pd[1] == want[3][1] and tuple(pd[0]) == tuple(want[3][0])
    assert xch.cap_version == version
    # the plans scale with the width: twice the lanes, twice the caps
    assert xch.plan_ex(widths[3], site=site)[2] \
        == 2 * xch.plan_ex(widths[1], site=site)[2]


def test_even_traffic_keeps_the_unmeasured_plan():
    """On four shards the unmeasured plan is the even per-destination
    layout, so a workload that spreads its lanes evenly measures the
    plan it started with at a slice of 2^k lanes: its first exchange
    program is its last."""
    e = TensorEngine(mesh=_mesh(4), initial_capacity=64)
    e.config.exchange_structured = "always"
    xch = e.exchange
    site = ("RouteSink", "recv")
    rng = np.random.default_rng(4)
    pow2 = [k * 65536 for k in (1, 2, 4, 8)]
    before = {m: xch.plan_ex(m, site=site) for m in pow2}
    assert all(plan[0] == "perdest" for plan in before.values())
    for k in range(1, 9):
        m = k * 65536
        per_src = rng.multinomial(m // 4, [0.25] * 4, size=4)
        np.fill_diagonal(per_src, 0)
        xch.observe_need(site, per_src.max(axis=0), per_src.sum(axis=0),
                         valid=m, width=m)
    for m in pow2:
        mode, L, cap, pd = xch.plan_ex(m, site=site)
        assert (mode, L, cap) == before[m][:3], m
        assert (pd[0], pd[1]) == (before[m][3][0], before[m][3][1]), m


@pytest.mark.parametrize("per_dest", ["never", "always"])
def test_undersized_estimate_parks_and_redelivers_under_churn(run,
                                                              per_dest):
    """THE safety property of occupancy sizing: a stale/undersized cap
    estimate may only ever park-and-redeliver — never drop, never
    double-deliver — across traffic shifts, arena growth, mesh
    reshards, and eviction-epoch bumps.  Verified by an exact host
    mirror of every delivery across randomized churn rounds.
    Parametrized over BOTH exchange bodies: the legacy max-over-dest
    cap and the per-destination grant vector — an undersized/stale
    per-dest grant must obey the identical conservation contract."""

    async def main():
        from orleans_tpu.tensor import MemoryVectorStore

        e = TensorEngine(mesh=_mesh(), initial_capacity=1024,
                         store=MemoryVectorStore())
        e.config.auto_fusion_ticks = 0
        e.config.exchange_structured = "always"
        e.config.exchange_per_dest = per_dest
        e.config.exchange_shrink_patience = 1  # shrink eagerly: the
        # estimate goes stale the moment traffic shifts back up
        n_src = 256
        src = np.arange(n_src, dtype=np.int64)
        sinks = list(range(SINK_BASE, SINK_BASE + 64))
        e.arena_for("RouteSource").resolve_rows(src)
        e.arena_for("RouteSink").resolve_rows(
            np.asarray(sinks, dtype=np.int64))
        mirror: dict = {}
        dropped_seen = 0
        tick = 0
        for rnd in range(8):
            # alternate tiny and huge cross ratios so the sized cap is
            # undersized on every upswing
            ratio = [0.0, 0.9][rnd % 2]
            sink_arr = np.asarray(sinks, dtype=np.int64)
            dst = build_ratio_destinations(src, sink_arr, e.n_shards,
                                           ratio, seed=rnd)
            inj = e.make_injector("RouteSource", "send", src)
            for _ in range(2):
                inj.inject({"dst": jnp.asarray(dst.astype(np.int32)),
                            "v": jnp.asarray(
                                np.ones(n_src, np.float32)),
                            "tick": np.int32(tick)})
                await e.drain_queues()
                tick += 1
                for d in dst:
                    mirror[int(d)] = mirror.get(int(d), 0) + 1
            await e.flush()
            dropped_seen = max(dropped_seen,
                               e.exchange.dropped_msgs)
            # churn between rounds: grow the sink set, bump eviction
            # epochs, and reshard the mesh mid-sequence
            if rnd == 2:
                sinks += list(range(SINK_BASE + 1000,
                                    SINK_BASE + 1000 + 512))
                e.arena_for("RouteSink").resolve_rows(
                    np.asarray(sinks, dtype=np.int64))
            if rnd == 4:
                # eviction-epoch bump: everything idle writes back to
                # the store and re-activates on the next delivery
                evicted = e.collect_idle(max_idle_ticks=0)
                assert evicted > 0
            if rnd == 5:
                await e.reshard(_mesh(4))
            if rnd == 6:
                await e.reshard(_mesh(N_DEV))
        # exact conservation: every injected delivery landed exactly
        # once, through however many parks/redeliveries it took
        arena = e.arena_for("RouteSink")
        keys = np.asarray(sorted(mirror), dtype=np.int64)
        # evicted-but-quiet sinks live only in the store — re-activate
        # (loads written-back state) before reading
        arena.resolve_rows(keys)
        rows, found = arena.lookup_rows(keys)
        assert found.all()
        got = np.asarray(arena.state["received"])[rows]
        want = np.asarray([mirror[int(k)] for k in keys])
        np.testing.assert_array_equal(got, want)
        # the interesting path actually ran: at least one upswing
        # overflowed the stale cap into a parked redelivery
        assert dropped_seen > 0
        assert e.exchange.redeliveries > 0

    run(main())


def test_cap_requantization_retraces_with_recorded_cause(run):
    """A cap re-quantization must surface as ONE cause-coded re-trace
    (bucket_growth) — never a silent recompile, and never a per-tick
    compile storm in steady state."""

    async def main():
        e = _engine(initial_capacity=1024)
        src = np.arange(512, dtype=np.int64)
        sinks = np.arange(SINK_BASE, SINK_BASE + 256, dtype=np.int64)
        e.arena_for("RouteSource").resolve_rows(src)
        e.arena_for("RouteSink").resolve_rows(sinks)
        dst = build_ratio_destinations(src, sinks, N_DEV, 0.5, seed=1)
        prog = e.fuse_ticks("RouteSource", "send", src)
        static = {"dst": jnp.asarray(dst.astype(np.int32)),
                  "v": jnp.asarray(np.ones(512, np.float32))}

        def win(t0):
            return {"tick": jnp.arange(2, dtype=jnp.int32) + t0}

        prog.run(win(0), static_args=static)   # fallback worst-case cap
        assert prog.verify() == 0              # folds measured demand
        causes0 = dict(e.compile_tracker.by_cause)
        prog.run(win(2), static_args=static)   # re-traces at tight cap
        assert prog.verify() == 0
        causes1 = dict(e.compile_tracker.by_cause)
        assert causes1["bucket_growth"] == causes0.get(
            "bucket_growth", 0) + 1, (causes0, causes1)
        # steady state: no further compiles, same program
        total = e.compile_tracker.total
        for i in range(3):
            prog.run(win(4 + 2 * i), static_args=static)
        assert prog.verify() == 0
        assert e.compile_tracker.total == total
        # the unfused dispatch records a re-quantization the same way:
        # same (L, shard_capacity, leaves) shape under a NEW cap
        xch = e.exchange
        arena = e.arena_for("RouteSink")
        rows = jnp.asarray(np.zeros(512, np.int32))
        mask = jnp.ones(512, bool)
        site = ("RouteSink", "probe_site")
        xch.observe_need(site, np.array([4] + [0] * (N_DEV - 1)),
                         valid=512, width=512)
        xch.dispatch(arena, rows, {"v": jnp.zeros(512)}, mask,
                     site=site)
        before = e.compile_tracker.by_cause.get("bucket_growth", 0)
        xch.observe_need(site, np.array([300] + [0] * (N_DEV - 1)),
                         valid=512, width=512)
        xch.dispatch(arena, rows, {"v": jnp.zeros(512)}, mask,
                     site=site)
        assert e.compile_tracker.by_cause["bucket_growth"] \
            == before + 1

    run(main())


# ---------------------------------------------------------------------------
# packed cross-lanes: host alignment + identity engagement + overlap
# ---------------------------------------------------------------------------

def test_fused_source_alignment_packs_and_skips_exchange(run):
    """A fused source with a static key set is packed home-shard-local
    at build (align_plan): the source leg traces NO exchange at all,
    the sink leg still exchanges, and the result is exact vs an
    unaligned window."""

    async def main():
        src = np.arange(512, dtype=np.int64)
        sinks = np.arange(SINK_BASE, SINK_BASE + 256, dtype=np.int64)
        dst = None
        results = {}
        for align in (True, False):
            e = _engine(initial_capacity=1024)
            e.config.exchange_align_sources = align
            e.arena_for("RouteSource").resolve_rows(src)
            e.arena_for("RouteSink").resolve_rows(sinks)
            if dst is None:
                dst = build_ratio_destinations(src, sinks, N_DEV, 0.5,
                                               seed=2)
            prog = e.fuse_ticks("RouteSource", "send", src)
            static = {"dst": jnp.asarray(dst.astype(np.int32)),
                      "v": jnp.asarray(np.ones(512, np.float32))}
            prog.run({"tick": jnp.arange(4, dtype=jnp.int32)},
                     static_args=static)
            assert prog.verify() == 0
            if align:
                assert prog._align[0] is not None
                # the aligned source leg skips the exchange entirely;
                # the sink (emit) leg still runs it
                assert "RouteSource.send" not in prog._exchange_sites
                assert "RouteSink.recv" in prog._exchange_sites
                # the packed layout really is home-shard-local
                al = prog._align[0]
                rows_a = np.asarray(al["rows"])
                La = len(rows_a) // N_DEV
                chunk = np.arange(len(rows_a)) // La
                cap_shard = e.arena_for("RouteSource").shard_capacity
                live = rows_a >= 0
                assert (rows_a[live] // cap_shard
                        == chunk[live]).all()
            else:
                assert prog._align[0] is None
            results[align] = _sink_state(e, 256)
        np.testing.assert_array_equal(results[True][0],
                                      results[False][0])
        np.testing.assert_array_equal(results[True][1],
                                      results[False][1])

    run(main())


def test_auto_mode_disengages_on_virtual_mesh_and_probes(run):
    """config.exchange_structured='auto' on a host-virtual CPU mesh:
    the structured path never runs (identity — delivery rides implicit
    collectives, bit-exact vs exchange-off), while the sampled probe
    still reports true cross traffic and demand."""

    async def main():
        e = TensorEngine(mesh=_mesh(), initial_capacity=1024)
        e.config.auto_fusion_ticks = 0
        e.config.exchange_probe_interval = 2
        assert not e.exchange.engaged()
        st = await run_routing_load(e, 512, 256, 0.5, n_ticks=4)
        assert st["messages_per_sec"] > 0
        xs = e.snapshot()["exchange"]
        # nothing structured ran …
        assert xs["exchanges_run"] == 0
        assert xs["dropped_msgs"] == 0
        # … yet the probe measured the real cross traffic and demand
        assert xs["cross_shard_msgs"] > 0
        assert any(v["peak_need"] and max(v["peak_need"]) > 0
                   for v in xs["sites"].values())
        # exact vs the exchange-off replay
        e_off = TensorEngine(mesh=_mesh(), initial_capacity=1024)
        e_off.config.auto_fusion_ticks = 0
        e_off.config.cross_shard_exchange = False
        await run_routing_load(e_off, 512, 256, 0.5, n_ticks=4)
        t_on, r_on = _sink_state(e, 256)
        t_off, r_off = _sink_state(e_off, 256)
        np.testing.assert_array_equal(t_on, t_off)
        np.testing.assert_array_equal(r_on, r_off)

    run(main())


def test_pre_exchange_overlap_credit(run):
    """Exchange overlap, unfused path: injector batches with cached
    resolutions pre-dispatch their exchange at round start; the
    consuming group collects the result and the credit (the wall the
    device had to hide the all_to_all in) accumulates — with delivery
    still exact."""

    async def main():
        e = _engine(initial_capacity=1024)
        assert e.config.exchange_overlap
        st = await run_routing_load(e, 512, 256, 0.5, n_ticks=6)
        assert st["messages_per_sec"] > 0
        xs = e.exchange
        assert xs.overlap_hits > 0
        assert xs.overlap_seconds >= 0.0
        assert e.snapshot()["exchange"]["overlap_seconds"] \
            == round(xs.overlap_seconds, 6)
        # exactness unchanged by the pre-dispatch path
        e_off = _engine(initial_capacity=1024)
        e_off.config.cross_shard_exchange = False
        await run_routing_load(e_off, 512, 256, 0.5, n_ticks=6)
        np.testing.assert_array_equal(_sink_state(e, 256)[1],
                                      _sink_state(e_off, 256)[1])

    run(main())
