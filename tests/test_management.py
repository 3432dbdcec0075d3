"""Admin surface: SiloControl, ManagementGrain fan-out, watchdog, CLI.

Reference analogs: ManagementGrain.cs:38 / SiloControl.cs:33 /
Watchdog.cs:32 / OrleansManager Program.cs.
"""

import asyncio

import numpy as np

from orleans_tpu.core.grain import grain_id_for
from orleans_tpu.runtime.management import IManagementGrain
from orleans_tpu.testing import TestingCluster

from tests.fixture_grains import ICounterGrain


def test_management_grain_fanout(run):
    """hosts/stats/grainstats/activations aggregate over every silo."""

    async def main():
        cluster = await TestingCluster(n_silos=3).start()
        try:
            await cluster.wait_for_liveness_convergence()
            factory = cluster.attach_client(0)
            refs = [factory.get_grain(ICounterGrain, 5000 + i)
                    for i in range(15)]
            await asyncio.gather(*(r.add(1) for r in refs))

            mgmt = factory.get_grain(IManagementGrain, 0)
            hosts = await mgmt.get_hosts()
            assert len(hosts) == 3
            assert all(v == "ACTIVE" for v in hosts.values())

            total = await mgmt.get_total_activation_count()
            # 15 counters + the management grain itself
            assert total >= 16, total

            stats = await mgmt.get_simple_grain_statistics()
            counter_total = sum(s.activation_count for s in stats
                                if s.grain_type == "CounterGrain")
            assert counter_total == 15, stats

            runtime_stats = await mgmt.get_runtime_statistics()
            assert len(runtime_stats) == 3
            assert sum(s.activation_count for s in runtime_stats) >= 16
        finally:
            await cluster.stop()

    run(main())


def test_management_lookup_and_unregister(run):
    """Directory repair path (reference: OrleansManager unregister)."""

    async def main():
        cluster = await TestingCluster(n_silos=2).start()
        try:
            await cluster.wait_for_liveness_convergence()
            factory = cluster.attach_client(0)
            ref = factory.get_grain(ICounterGrain, 5555)
            await ref.add(1)
            mgmt = factory.get_grain(IManagementGrain, 0)

            gid = grain_id_for(ICounterGrain, 5555)
            found = await mgmt.lookup(gid)
            assert found is not None and "silo" in found, found

            assert await mgmt.unregister(gid) is True
            # the directory entry is actually gone
            assert await mgmt.lookup(gid) is None
            # and a fresh call re-activates cleanly
            assert await ref.add(1) >= 1
        finally:
            await cluster.stop()

    run(main())


def test_silo_control_forced_collection(run):
    """force_activation_collection(0) deactivates idle activations
    cluster-wide (reference: ForceActivationCollection)."""

    async def main():
        cluster = await TestingCluster(n_silos=2).start()
        try:
            await cluster.wait_for_liveness_convergence()
            factory = cluster.attach_client(0)
            refs = [factory.get_grain(ICounterGrain, 5600 + i)
                    for i in range(10)]
            await asyncio.gather(*(r.add(1) for r in refs))
            before = cluster.total_activations()
            assert before >= 10

            mgmt = factory.get_grain(IManagementGrain, 0)
            collected = await mgmt.force_activation_collection(0.0)
            assert collected >= 10
            # deactivations are scheduled; let them run
            await asyncio.sleep(0.1)
            assert cluster.total_activations() < before
        finally:
            await cluster.stop()

    run(main())


def test_silo_control_tensor_stats_and_collection(run):
    """The admin surface covers the tensor plane too."""

    async def main():
        from orleans_tpu.runtime.silo import Silo
        from samples.presence import PresenceGrain  # registers vector type

        silo = Silo(name="mgmt-tensor")
        await silo.start()
        try:
            engine = silo.tensor_engine
            engine.send_batch("PresenceGrain", "heartbeat",
                              np.arange(20, dtype=np.int64),
                              {"game": np.zeros(20, np.int32),
                               "score": np.ones(20, np.float32),
                               "tick": np.full(20, 1, np.int32)})
            await engine.flush()

            control = silo.system_targets["silo_control"]
            stats = await control.get_simple_grain_statistics()
            tensor_rows = {s.grain_type: s.activation_count
                           for s in stats if s.plane == "tensor"}
            assert tensor_rows.get("PresenceGrain") == 20, stats

            # idle_ticks=0 collects rows idle since before the current
            # tick (rows touched AT the current tick survive the sweep)
            collected = await control.force_tensor_collection(0)
            assert collected >= 20, collected
        finally:
            await silo.stop()

    run(main())


def test_watchdog_detects_dead_participant(run):
    async def main():
        from orleans_tpu.config import SiloConfig
        from orleans_tpu.runtime.silo import Silo

        cfg = SiloConfig(name="watchdog-test")
        cfg.watchdog_period = 0.05
        silo = Silo(config=cfg)
        await silo.start()
        try:
            wd = silo.watchdog
            assert wd is not None and wd._running

            class Sick:
                def check_health(self):
                    return False

            class Throwing:
                def check_health(self):
                    raise RuntimeError("boom")

            wd.register(Sick())
            wd.register(Throwing())
            failures = wd.check_participants()
            assert failures == 2
            # healthy built-ins don't fail: re-check only them
            wd.participants = [p for p in wd.participants
                               if not isinstance(p, (Sick, Throwing))]
            assert wd.check_participants() == 0
        finally:
            await silo.stop()

    run(main())


def test_watchdog_detects_loop_stall(run):
    async def main():
        from orleans_tpu.config import SiloConfig
        from orleans_tpu.runtime.silo import Silo
        import time

        cfg = SiloConfig(name="stall-test")
        cfg.watchdog_period = 0.05
        silo = Silo(config=cfg)
        await silo.start()
        try:
            wd = silo.watchdog
            wd.stall_threshold = 0.1
            await asyncio.sleep(0.1)   # let the loop settle into a sleep
            time.sleep(0.4)            # synchronously hog the event loop
            await asyncio.sleep(0.15)  # watchdog wakes late, records stall
            assert wd.loop_stalls >= 1
        finally:
            await silo.stop()

    run(main())


def test_watchdog_thread_names_the_stage_and_frame_of_a_stall(run,
                                                              caplog):
    """The watchdog thread sees a stall while it lasts: the innermost
    stage open on the loop thread and that thread's frames go into the
    [code 3001] warning, the incident bundle and the per-stage stall
    seconds."""
    async def main():
        import logging
        import time

        from orleans_tpu.config import SiloConfig
        from orleans_tpu.runtime.silo import Silo
        from orleans_tpu.tensor.profiler import Stage

        cfg = SiloConfig(name="stall-stage-test")
        cfg.watchdog_period = 0.05
        silo = Silo(config=cfg)
        await silo.start()
        try:
            wd = silo.watchdog
            wd.stall_threshold = 0.5
            await asyncio.sleep(0.2)

            def block_the_loop_in_a_stage():
                with Stage("orleans.test.blocked"):
                    time.sleep(1.5)

            with caplog.at_level(logging.WARNING):
                block_the_loop_in_a_stage()
                await asyncio.sleep(0.3)  # the heartbeat reports
            stall = wd.last_stall
            assert stall is not None, "no stall reported"
            assert stall["stage"] == "orleans.test.blocked"
            assert stall["seconds"] >= 1.0
            assert any("block_the_loop_in_a_stage" in f
                       for f in stall["frames"]), stall["frames"]
            assert wd.stall_seconds_by_stage["orleans.test.blocked"] \
                >= 1.0
            warned = [r.getMessage() for r in caplog.records
                      if "[code 3001]" in r.getMessage()]
            assert any("orleans.test.blocked" in m
                       and "block_the_loop_in_a_stage" in m
                       for m in warned), warned
            bundle = list(silo.incidents)[-1]
            assert "orleans.test.blocked" in bundle["reason"]
            assert bundle["stall"]["frames"] == stall["frames"]
            thread = wd._thread
        finally:
            await silo.stop()
        assert not thread.is_alive()  # stopped with the silo

    run(main())


def test_manager_cli_commands(run, tmp_path, capsys):
    """The CLI joins via the shared membership table, runs commands
    through the management grain, and leaves (reference: OrleansManager)."""

    async def main():
        from orleans_tpu.host import build_silo
        from orleans_tpu.manager import run_command

        db = str(tmp_path / "cli-cluster.db")
        cfg = {"host": "127.0.0.1", "membership_db": db,
               "storage": {"Default": {"kind": "memory"}},
               "silo": {"liveness": {
                   "probe_period": 0.1, "probe_timeout": 0.1,
                   "num_missed_probes_limit": 2,
                   "table_refresh_timeout": 0.2,
                   "iam_alive_table_publish": 0.5}}}
        silo = build_silo({**cfg, "name": "cli-host"})
        await silo.start()
        try:
            factory = silo.attach_client()
            await asyncio.gather(*(factory.get_grain(ICounterGrain,
                                                     5700 + i).add(1)
                                   for i in range(5)))
            hosts = await run_command(cfg, "hosts", [])
            assert any("ACTIVE" == v for v in hosts.values())
            total = await run_command(cfg, "activations", [])
            assert total >= 5
            stats = await run_command(cfg, "grainstats", [])
            assert any("CounterGrain" in line for line in stats)
        finally:
            await silo.stop()

    run(main())


def test_non_hosting_member_gets_no_placements(run, tmp_path):
    """A host_grains=False member (the CLI's mode) joins membership but
    never receives grain placements and takes no ring ranges."""

    async def main():
        from orleans_tpu.host import build_silo

        db = str(tmp_path / "observer-cluster.db")
        cfg = {"host": "127.0.0.1", "membership_db": db,
               "storage": {"Default": {"kind": "memory"}},
               "silo": {"liveness": {
                   "probe_period": 0.1, "probe_timeout": 0.1,
                   "num_missed_probes_limit": 2,
                   "table_refresh_timeout": 0.2,
                   "iam_alive_table_publish": 0.5}}}
        host = build_silo({**cfg, "name": "real-host"})
        observer_cfg = {**cfg, "name": "observer",
                        "silo": {**cfg["silo"], "host_grains": False,
                                 "gateway_enabled": False,
                                 "reminders": {"enabled": False},
                                 "tensor": {"enabled": False}}}
        observer = build_silo(observer_cfg)
        await host.start()
        await observer.start()
        try:
            deadline = asyncio.get_running_loop().time() + 10
            while not (len(host.active_silos()) == 2
                       and len(observer.active_silos()) == 2):
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.05)

            # placement-eligible set excludes the observer everywhere
            assert host.hosting_silos() == [host.address]
            assert observer.hosting_silos() == [host.address]
            # the observer never joined the real host's ring
            assert observer.address not in host.ring.members

            # activations driven from the observer all land on the host
            factory = observer.attach_client()
            refs = [factory.get_grain(ICounterGrain, 5800 + i)
                    for i in range(8)]
            await asyncio.gather(*(r.add(1) for r in refs))
            assert len(observer.catalog.directory) == 0
            assert len(host.catalog.directory) >= 8
        finally:
            await observer.stop()
            await host.stop()

    run(main())


def test_tensor_statistics_fanout(run):
    """Tick-engine counters (throughput, true latency percentiles, arena
    sizes) flow through the management surface."""

    def patient_liveness(name):
        # the presence load's jit compiles stall the event loop for
        # longer than the default test liveness budget (probe 0.1s × 2
        # missed) — under file-level cache timing both silos could vote
        # each other DEAD mid-test and the fan-out read an empty
        # membership view.  This test is about the management surface,
        # not liveness: give probes compile-sized patience.
        cfg = TestingCluster._default_config(name)
        cfg.liveness.probe_period = 1.0
        cfg.liveness.probe_timeout = 2.0
        cfg.liveness.num_missed_probes_limit = 5
        return cfg

    async def main():
        cluster = await TestingCluster(
            n_silos=2, config_factory=patient_liveness).start()
        try:
            await cluster.wait_for_liveness_convergence()
            # put some tensor traffic on silo 0's engine
            from samples.presence import run_presence_load
            await run_presence_load(cluster.silos[0].tensor_engine,
                                    n_players=300, n_games=3, n_ticks=3)

            factory = cluster.attach_client(0)
            mgmt = factory.get_grain(IManagementGrain, 0)
            stats = await mgmt.get_tensor_statistics()
            assert len(stats) >= 1
            # the vector router splits the load by ring owner, so the
            # cluster-wide totals (what the admin surface is for) carry
            # the traffic, spread over the member silos
            assert sum(s["messages"] for s in stats) >= 2 * 300 * 3
            busy = max(stats, key=lambda s: s["messages"])
            lat = busy["tick_latency"]
            assert lat["n"] > 0 and lat["p99"] >= lat["p50"] > 0
            assert sum(s["arenas"].get("PresenceGrain", 0)
                       for s in stats) == 300
        finally:
            await cluster.stop()

    run(main())
