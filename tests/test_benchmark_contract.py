"""The program's side of the benchmark's contract (``benchmark/``).

The benchmark reads two things from the silo it drives, and neither is
exercised by ``benchmark/``'s own tests without a chip:

* its per-layer readers add up device time by the module name a jitted
  program gets in the trace (``jit_<function name>``);
* ``benchmark/harness.py`` wraps ``send_batch``, ``pipeline.note_tick``
  and ``run_tick`` on the live engine, and reads ``tick_number``,
  ``compile_count()``, ``compile_tracker`` and ``profiler.snapshot()``.

A program change that renames or re-routes any of them makes a metric
read 0 with nothing failing before the chip run.  Here each cell's
configuration is built with ``orleans_tpu.host.build_silo`` at a few
thousand grains on the CPU, fed with the benchmark's own apps and
generator, and checked against the names the readers list (read from
their files, so the two cannot drift) and the hooks the harness
installs (its own ``Hooks`` class).
"""

import asyncio
import importlib
import importlib.util
import json
import logging
import re
import sys
from collections import Counter
from pathlib import Path
from typing import Optional

import jax
import numpy as np
import pytest

from orleans_tpu.client import GrainClient
from orleans_tpu.host import build_silo

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmark"

#: each configuration of BENCHMARK.json cut to a few thousand grains (a
#: configuration added there needs its size here)
SIZES = {
    "presence-1m": {"players": 4096, "games": 64},
    "chirper-100k": {"accounts": 4096, "mean_followers": 8.0},
    "presence-4m": {"players": 16384, "games": 256},
}
#: lanes per slab at that size; slabs in flight are capped at IN_FLIGHT
LANES = {"presence": 512, "chirper": 512}
IN_FLIGHT = 2
SEED = 7


def _load(rel: str, name: str):
    """A file of ``benchmark/`` as module ``name`` (registered, as a
    dataclass's annotations are resolved through ``sys.modules``)."""
    spec = importlib.util.spec_from_file_location(name, BENCH / rel)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _harness():
    """``benchmark/harness.py``, which imports ``spec`` from its own
    directory: that one module is visible only while it loads."""
    before = sys.modules.get("spec")
    sys.modules["spec"] = _load("spec.py", "spec")
    try:
        return _load("harness.py", "_bench_harness")
    finally:
        if before is None:
            del sys.modules["spec"]
        else:
            sys.modules["spec"] = before


def _reader_modules():
    """Every module name a per-layer reader adds up, in reader order."""
    names = list(_load("metrics/observability_device_share.bulk.py",
                       "_bench_observability").MODULES)
    names += _load("roofline_exchange.py", "_bench_roofline_exchange").MODULES
    for group in _load("roofline.py", "_bench_roofline").GROUPS.values():
        names += group
    return list(dict.fromkeys(names))


def _benchmark() -> dict:
    with open(REPO / "BENCHMARK.json") as f:
        return json.load(f)


class Cell:
    """One configuration at its test size, with the traffic mix of its
    first workload."""

    def __init__(self, name: str, silo_extra: Optional[dict] = None) -> None:
        bench = _benchmark()
        file = next(c["file"] for c in bench["configs"] if c["name"] == name)
        with open(REPO / file) as f:
            self.config = dict(json.load(f), **SIZES[name])
        traffic = next(w["traffic"] for w in bench["workloads"]
                       if w["config"] == name)
        with open(BENCH / "traffic" / f"{traffic}.json") as f:
            self.mix = json.load(f)
        self.app = _load(f"apps/{self.config['app']}.py",
                         f"_bench_app_{self.config['app']}")
        self.gen = _load(f"generators/{self.mix['generator']}.py",
                         f"_bench_gen_{self.mix['generator']}")
        self.mix.update(lanes=LANES[self.config["app"]],
                        in_flight=min(IN_FLIGHT, int(self.mix["in_flight"])))
        self.draws = self.app.Draws(self.config, SEED)
        silo = dict(self.config.get("silo_config", {}))
        if silo_extra:
            silo["tensor"] = dict(silo.get("tensor", {}), **silo_extra)
        self.silo_config = silo
        importlib.import_module(self.config["grain_module"])

    async def boot(self):
        """The silo the harness builds, with every grain activated."""
        silo = build_silo({"name": "bench", "host": "127.0.0.1",
                           "storage": {"Default": {"kind": "memory"}},
                           "default_stats_log": False,
                           "silo": self.silo_config})
        await silo.start()
        self.app.activate(silo.tensor_engine, self.draws)
        return silo

    async def warm(self, engine, first_id: int = 0) -> int:
        """The harness's warm ticks: 1 to ``in_flight`` slabs a tick,
        each through the batch edge as the gateway hands it over."""
        next_id = first_id
        for batches in self.gen.warm_ticks(self.mix):
            futs = []
            for _ in range(batches):
                futs.append(self.gen.warm_send(engine, self.app,
                                               self.draws, self.mix,
                                               next_id))
                next_id += 1
            await asyncio.gather(*futs)
            await engine.flush()
            await engine.wait_completion()
        return next_id


class Compiles(logging.Handler):
    """The XLA module names compiled while it is installed.  JAX logs
    each compile with its ``jit(<name>)`` name; the module takes that
    name made a symbol, as ``jax._src.interpreters.mlir`` does."""

    def __init__(self) -> None:
        super().__init__(logging.WARNING)
        self.names = []

    def emit(self, record) -> None:
        if str(record.msg).startswith("Compiling %s"):
            self.names.append(
                re.sub(r"[^\w.-]", "_", str(record.args[0])).rstrip("_"))

    def __enter__(self):
        self._was = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax._src.interpreters.pxla").addHandler(self)
        return self

    def __exit__(self, *exc) -> None:
        logging.getLogger("jax._src.interpreters.pxla").removeHandler(self)
        jax.config.update("jax_log_compiles", self._was)


# ---------------------------------------------------------------------------
# the readers' module names
# ---------------------------------------------------------------------------

#: the path on which each program runs: the cell, the steps after
#: activation (``slabs``, then ``folds``, ``snapshot``, ``evict``), and
#: silo keys beyond the configuration's own
PATHS = {
    "jit_step_fn": ("presence-1m", "slabs"),
    "jit__plan_kernel": ("presence-1m", "slabs"),
    "jit__count_rows_kernel": ("presence-1m", "slabs"),
    "jit__apply_coalesced": ("presence-1m", "folds"),
    "jit__apply_checked_stack": ("presence-1m", "folds"),
    "jit__snapshot_kernel": ("presence-1m", "snapshot"),
    "jit__gather_counts": ("presence-1m", "evict"),
    "jit__zero_rows": ("presence-1m", "evict"),
    "jit__expand_kernel": ("chirper-100k", "slabs"),
    # on the CPU "auto" keeps the exchange disengaged: the probe runs
    "jit__exchange_probe": ("presence-4m", "slabs"),
    # on a chip "auto" engages it, which "always" forces here
    "jit__exchange_kernel": ("presence-4m", "slabs",
                             (("exchange_structured", "always"),)),
}


def _compiled_by_step(name: str, extra: tuple = ()) -> dict:
    """Module names compiled from activation through each step, with
    every JAX cache cleared first."""

    async def main():
        cell = Cell(name, dict(extra))
        jax.clear_caches()
        silo = await cell.boot()
        engine = silo.tensor_engine
        seen, out = set(), {}
        try:
            with Compiles() as compiles:
                # the warm ticks, then steady ticks of one slab each
                first = await cell.warm(engine)
                for i in range(first, first + 4):
                    fut = cell.gen.warm_send(engine, cell.app, cell.draws,
                                             cell.mix, i)
                    await engine.flush()
                    await fut
                await engine.wait_completion()
                seen.update(compiles.names)
                out["slabs"] = set(seen)
                engine.attribution.flush_folds()
                seen.update(compiles.names)
                out["folds"] = set(seen)
                silo.collect_metrics()
                engine.attribution.snapshot(cache=False)
                seen.update(compiles.names)
                out["snapshot"] = set(seen)
                arena = engine.arena_for(cell.app.GRAIN)
                rows, found = arena.lookup_rows(
                    np.arange(64, dtype=np.int64))
                assert found.all()
                arena.deactivate_idle_rows(rows, 10**9, write_back=False)
                seen.update(compiles.names)
                out["evict"] = set(seen)
        finally:
            await silo.stop()
        return out

    return asyncio.run(main())


@pytest.fixture(scope="module")
def compiled_on():
    """``_compiled_by_step``, run once per path in this module."""
    runs = {}

    def get(name: str, extra: tuple = ()) -> dict:
        if (name, extra) not in runs:
            runs[name, extra] = _compiled_by_step(name, extra)
        return runs[name, extra]

    return get


@pytest.mark.parametrize("module", _reader_modules())
def test_reader_module_is_compiled(compiled_on, module):
    """The cell's path compiles a program of the name the reader adds
    up: stronger than the function existing, it fails as well when the
    path stops calling it."""
    assert module in PATHS, f"{module}: a reader lists it, no path here"
    name, step, *extra = PATHS[module]
    compiled = compiled_on(name, *extra)[step]
    assert module in compiled, \
        f"{module} not compiled on {name} through {step!r}: {sorted(compiled)}"


# ---------------------------------------------------------------------------
# the hooks the harness installs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", [c["name"] for c in _benchmark()["configs"]])
def test_engine_hooks_the_harness_uses(run, name):
    """Slabs over the TCP gateway reach the harness's hooks: each tick
    that applied one is noted once under its ``tick_number``; the
    compile counters move only across a step-program compile; the
    profiler counts every tick run."""
    cell = Cell(name)
    hooks_cls = _harness().Hooks
    app, mix = cell.app, cell.mix
    lanes = mix["lanes"]

    async def main():
        silo = await cell.boot()
        engine = silo.tensor_engine
        try:
            hooks = hooks_cls(engine, app.GRAIN, app.METHOD,
                              lambda args: app.request_id(args, lanes),
                              True)
            ran = []
            run_tick = engine.run_tick

            def counted_run_tick():
                ran.append(engine.tick_number)
                run_tick()

            engine.run_tick = counted_run_tick
            next_id = await cell.warm(engine)
            warmed = list(range(next_id))

            client = await GrainClient().connect(
                ("127.0.0.1", silo.gateway_port))
            try:
                async def slabs(first, n, width):
                    futs = []
                    for i in range(first, first + n):
                        keys, args = cell.draws.slab(i, width)
                        futs.append(client.send_batch(
                            app.GRAIN, app.METHOD, keys, args,
                            want_results=True))
                        if len(futs) >= mix["in_flight"]:
                            await asyncio.gather(*futs)
                            futs = []
                    await asyncio.gather(*futs)
                    await engine.flush()
                    await engine.wait_completion()

                spans = []
                for n, width in ((4, lanes), (1, lanes // 4)):
                    before = (engine.compile_count(),
                              engine.compile_tracker.total,
                              engine.profiler.snapshot()["ticks_observed"],
                              len(ran))
                    with Compiles() as compiles:
                        await slabs(next_id, n, width)
                    # ``request_id`` names ``lanes``-wide slabs only
                    ids = list(range(next_id, next_id + n)) \
                        if width == lanes else []
                    next_id += n
                    spans.append((before, (
                        engine.compile_count(),
                        engine.compile_tracker.total,
                        engine.profiler.snapshot()["ticks_observed"],
                        len(ran)), "jit_step_fn" in compiles.names, ids))
            finally:
                await client.close()
            return hooks, warmed, spans, engine.profiler.snapshot()
        finally:
            await silo.stop()

    hooks, warmed, spans, snap = run(main())

    assert hooks.results_ok
    # every slab, warm or over TCP, passed the wrapped batch edge
    sent = warmed + [i for _, _, _, ids in spans for i in ids]
    assert set(sent) <= set(hooks.applied), \
        sorted(set(sent) - set(hooks.applied))
    noted = Counter(t for t, _, _ in hooks.ticks)
    for rid, tick in hooks.applied.items():
        assert noted[tick] == 1, (rid, tick, noted[tick])

    for (b, a, step_compiled, _) in spans:
        assert (a[0] > b[0]) == step_compiled, (b, a, step_compiled)
        assert (a[1] > b[1]) == step_compiled, (b, a, step_compiled)
        # the profiler observed each tick the engine ran
        assert a[2] - b[2] == a[3] - b[3] > 0, (b, a)
    # a slab of a lane bucket the warm-up never filled compiles
    assert spans[-1][2], spans

    assert set(snap["phase_seconds"]) and snap["ticks_observed"] > 0
