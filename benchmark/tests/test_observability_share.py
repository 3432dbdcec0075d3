"""``observability_device_share.bulk``: the metrics planes' device time
over the device's busy time, read from a reduced trace."""

from __future__ import annotations

import importlib
import pkgutil
from types import SimpleNamespace as NS

import pytest

import spec

READER = spec._reader(spec.REPO, "observability_device_share.bulk")


def _window(by_module, busy_s=10.0, platform="tpu"):
    return NS(trace={"busy_s": busy_s, "window_s": 20.0,
                     "by_module": by_module},
              platform=platform)


def test_reads_the_planes_share_of_busy_time():
    w = _window({"jit__plan_kernel": 2.0, "jit__apply_checked_stack": 3.0,
                 "jit__count_rows_kernel": 0.5, "jit_step_fn": 4.0,
                 "jit__expand_kernel": 0.5})
    assert READER.read(w) == pytest.approx(55.0)


def test_planes_absent_read_zero():
    assert READER.read(_window({"jit_step_fn": 4.0})) == 0.0


@pytest.mark.parametrize("w", [
    NS(trace=None, platform="tpu"),              # an untraced run
    _window({"jit__plan_kernel": 1.0}, platform="cpu"),
    _window({}, busy_s=0.0),
])
def test_nothing_to_read(w):
    assert READER.read(w) is None


def _jitted_functions():
    """name → jitted function, over the program's device-side modules."""
    import orleans_tpu.tensor

    out = {}
    for info in pkgutil.walk_packages(orleans_tpu.tensor.__path__,
                                      "orleans_tpu.tensor."):
        mod = importlib.import_module(info.name)
        for name, fn in vars(mod).items():
            if callable(fn) and hasattr(fn, "lower") \
                    and getattr(fn, "__module__", None) == info.name:
                out[name] = fn
    return out


def test_every_listed_module_is_a_program_of_the_planes():
    """A rename in the program fails here instead of reading 0."""
    jitted = _jitted_functions()
    for module in READER.MODULES:
        assert module.startswith("jit_"), module
        name = module[len("jit_"):]
        assert name in jitted, f"{module}: no jitted {name} in orleans_tpu"
        assert jitted[name].__module__ in ("orleans_tpu.tensor.attribution",
                                           "orleans_tpu.tensor.ledger")
