"""Standalone silo host: ``python -m orleans_tpu.host --config silo.json``.

Parity: reference OrleansHost — a console/service process that loads
config, constructs one Silo, starts it, and blocks until shutdown
(reference: src/OrleansHost/Program.cs:29 Main → WindowsServerHost.cs:36
Init/Run; SiloHost.cs LoadOrleansConfig/StartOrleansSilo).

A real multi-process cluster on one machine::

    python -m orleans_tpu.host --config a.json &
    python -m orleans_tpu.host --config b.json &

where both configs point at the same sqlite membership/reminder paths —
the sqlite tables are the cross-process CAS store (the reference's
SQL/Azure table role) and silo↔silo traffic rides TcpTransport (DCN).

Config file (JSON; every key optional)::

    {
      "name": "silo-a",
      "host": "127.0.0.1",          # routable endpoint peers dial
      "port": 0,                    # 0 = OS-assigned
      "membership_db": "cluster.db",  # shared sqlite path (omit = solo)
      "reminder_db": "cluster.db",
      "imports": ["myapp.grains"],  # app modules to import (registers
                                    # grain classes — the assembly-load
                                    # analog; also needed by the admin
                                    # CLI for lookup/unregister keys)
      "storage": {"Default": {"kind": "file", "root": "./state"}},
      "providers": [            # generic named provider blocks
        {"kind": "storage", "type": "sqlite", "name": "Audit",
         "path": "audit.db"},
        {"kind": "stream", "type": "simple", "name": "SMS"},
        {"kind": "bootstrap", "type": "myapp.boot:Warmup", "name": "warm"},
        {"kind": "statistics", "type":
         "orleans_tpu.plugins.stats_publisher:LogStatisticsPublisher",
         "name": "log"}
      ],
      "startup": "myapp.startup:configure",  # DI hook: fn(silo) registers
                                             # silo.services entries
      "silo": { ... SiloConfig.from_dict overrides ... }
    }
"""

from __future__ import annotations

import argparse
import asyncio
import json
import signal
from typing import Any, Dict, Optional

from orleans_tpu.config import SiloConfig
from orleans_tpu.runtime.silo import Silo
from orleans_tpu.runtime.transport import TcpFabric


def build_storage_providers(spec: Dict[str, Any]) -> Dict[str, Any]:
    """Shorthand ``storage`` blocks → instances.  One registry: delegates
    to the ProviderLoader's storage factories so the shorthand and the
    generic ``providers`` blocks accept exactly the same types
    (reference: <Provider Type=... Name=...> via ProviderLoader)."""
    from orleans_tpu.providers.loader import ProviderLoader, _resolve_type

    registry = ProviderLoader().registry
    out = {}
    for name, cfg in (spec or {}).items():
        kind = cfg.get("kind", "memory")
        props = {k: v for k, v in cfg.items() if k != "kind"}
        out[name] = _resolve_type("storage", kind, registry)(props)
    return out


def build_silo(config: Dict[str, Any],
               fabric: Optional[TcpFabric] = None) -> Silo:
    """Construct (but do not start) a silo from a host config dict."""
    import importlib
    for mod in config.get("imports", ()):
        # application grain modules register their classes on import
        # (reference: SiloAssemblyLoader directory scan, Silo.cs:433)
        importlib.import_module(mod)
    silo_cfg = SiloConfig.from_dict({"name": config.get("name", "silo"),
                                     **config.get("silo", {})})
    host = config.get("host", "127.0.0.1")
    fabric = fabric or TcpFabric(host=host)
    port = int(config.get("port", 0)) or fabric.reserve()

    membership_table = None
    reminder_table = None
    if config.get("table_service"):
        # networked system tables: machines with NO shared disk form a
        # cluster by pointing at one table service endpoint
        # ("host:port" or {"host":..., "port":...}) — the reference's
        # ZooKeeper/SQL/Azure table role (plugins/table_service.py)
        from orleans_tpu.plugins.table_service import (
            RemoteMembershipTable,
            RemoteReminderTable,
        )
        spec = config["table_service"]
        if isinstance(spec, str):
            ts_host, _, ts_port = spec.rpartition(":")
            spec = {"host": ts_host or "127.0.0.1", "port": int(ts_port)}
        membership_table = RemoteMembershipTable(spec["host"],
                                                 int(spec["port"]))
        reminder_table = RemoteReminderTable(spec["host"],
                                             int(spec["port"]))
    if membership_table is None and config.get("membership_db"):
        from orleans_tpu.plugins.sqlite_tables import SqliteMembershipTable
        membership_table = SqliteMembershipTable(config["membership_db"])
    elif membership_table is None and config.get("membership_file"):
        from orleans_tpu.plugins.file_tables import FileMembershipTable
        membership_table = FileMembershipTable(config["membership_file"])
    if reminder_table is None and config.get("reminder_db"):
        from orleans_tpu.plugins.sqlite_tables import SqliteReminderTable
        reminder_table = SqliteReminderTable(config["reminder_db"])
    elif reminder_table is None and config.get("reminder_file"):
        from orleans_tpu.plugins.file_tables import FileReminderTable
        reminder_table = FileReminderTable(config["reminder_file"])

    silo = Silo(
        config=silo_cfg,
        storage_providers=build_storage_providers(config.get("storage", {})),
        fabric=fabric,
        membership_table=membership_table,
        reminder_table=reminder_table,
        host=host, port=port,
    )
    # generic named provider blocks (reference: ProviderLoader over
    # <Provider Type=... Name=...> config)
    if config.get("providers"):
        from orleans_tpu.providers.loader import ProviderLoader
        ProviderLoader().load(silo, config["providers"])
    # DI/startup hook (reference: ConfigureStartupBuilder.cs:40): the
    # named function receives the silo and registers silo.services
    if config.get("startup"):
        from orleans_tpu.providers.loader import load_attr
        result = load_attr(config["startup"])(silo)
        if isinstance(result, dict):
            silo.services.update(result)
    if not silo.statistics_publishers \
            and config.get("default_stats_log", True):
        # hosted silos dump their metrics periodically by default
        # (reference: LogStatistics.cs:33 'DumpCounters' runs out of the
        # box); disable with "default_stats_log": false or replace via a
        # statistics provider block
        from orleans_tpu.plugins.stats_publisher import (
            LogStatisticsPublisher,
        )
        silo.statistics_publishers["log"] = LogStatisticsPublisher()
    return silo


async def run_host(config: Dict[str, Any],
                   shutdown: Optional[asyncio.Event] = None,
                   config_path: Optional[str] = None,
                   reload_poll: float = 2.0,
                   on_started=None) -> None:
    """Start a silo and serve until ``shutdown`` is set (or SIGINT/SIGTERM
    arrives) — reference: WindowsServerHost.Run's wait loop.

    When ``config_path`` is given the file is polled for changes and the
    ``silo`` section is live-applied via Silo.update_config (reference:
    live-reload OnConfigChange hooks; identity/topology keys require a
    restart and are ignored)."""
    import os

    silo = build_silo(config)
    shutdown = shutdown or asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, shutdown.set)
        except (NotImplementedError, RuntimeError):
            pass  # non-main thread / platform without signal support
    await silo.start()
    print(f"silo {silo.name} active at {silo.address.host}:"
          f"{silo.address.port}", flush=True)
    if on_started is not None:
        on_started(silo)  # embedding/test hook: observe the live silo

    async def watch_config() -> None:
        mtime: Optional[float] = None
        while True:
            try:
                m = os.path.getmtime(config_path)
                if mtime is None:
                    mtime = m
                elif m != mtime:
                    mtime = m
                    with open(config_path) as f:
                        fresh = json.load(f)
                    silo.update_config(fresh.get("silo") or {})
                    print(f"silo {silo.name}: config reloaded", flush=True)
            except (OSError, json.JSONDecodeError):
                pass  # transient editor states; keep watching
            except Exception as exc:  # noqa: BLE001 — a bad edit must not
                # silently kill the watcher (future edits still apply)
                print(f"silo {silo.name}: config reload rejected: {exc}",
                      flush=True)
            await asyncio.sleep(reload_poll)

    watcher = None
    if config_path is not None:
        watcher = loop.create_task(watch_config())
    try:
        await shutdown.wait()
    finally:
        if watcher is not None:
            watcher.cancel()
        await silo.stop()
        print(f"silo {silo.name} stopped", flush=True)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        prog="python -m orleans_tpu.host",
        description="Run one silo from a JSON config (reference: "
                    "OrleansHost.exe <deployment.xml>)")
    parser.add_argument("--config", help="path to JSON host config")
    parser.add_argument("--name", default=None, help="override silo name")
    args = parser.parse_args(argv)
    from orleans_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    config: Dict[str, Any] = {}
    if args.config:
        with open(args.config) as f:
            config = json.load(f)
    if args.name:
        config["name"] = args.name
    asyncio.run(run_host(config, config_path=args.config))


if __name__ == "__main__":
    main()
