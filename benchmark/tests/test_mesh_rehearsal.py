"""The four-chip cell rehearsed on a 4-device CPU mesh, and the readers
of the exchange plane's two metrics.

The rehearsal runs ``presence-4chip-bulk``, cut to a tiny size in a
throwaway root, through ``run.main`` in a child process whose XLA host
platform shows four devices: the silo builds its engine over them from
the configuration's ``mesh_devices``.  The run is correct, and the
control is not."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

import roofline_exchange as rx
import spec
import tiny

CELL = "presence-4chip-bulk-tiny"
SHARE = spec._reader(spec.REPO, "exchange_device_share.mesh")
ROOFLINE = spec._reader(spec.REPO, "presence_exchange_roofline")
V5E = "TPU v5 lite"

_CHILD = """
import sys, time
sys.path[:0] = [{bench!r}, {repo!r}]
import run
sys.exit(run.main(["--workload", {cell!r}, "--seed", {seed!r},
                   "--seconds", "1.5", "--trace", "0",
                   "--control", {control!r}],
                  allow_cpu=True, root={root!r}, t_start=time.monotonic()))
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def run_cell(root, control: int, seed: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _CHILD.format(bench=tiny.BENCH, repo=tiny.REPO, cell=CELL,
                         seed=str(seed), control=str(control), root=root)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_cell_asks_for_four_chips_and_a_mesh(root):
    cell = spec.load_cell(CELL, root)
    assert cell.chips == 4
    assert cell.config["silo_config"]["tensor"]["mesh_devices"] == 4


@pytest.mark.parametrize("control", [0, 1])
def test_mesh_rehearsal(root, control):
    res = run_cell(root, control, seed=3_100_000_001 + control)
    assert res["device"]["count"] == 4
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["correct"] is (control == 0), res["checks"]
    if control:
        assert any(c["value"] > c["limit"] for c in res["checks"].values())
    assert res["run"]["window_compiles"] == 0


# ---------------------------------------------------------------------------
# the readers, on a synthetic reduced trace
# ---------------------------------------------------------------------------

def _window(by_module, chips=4, work=None, busy_s=10.0, platform="tpu"):
    return NS(trace={"busy_s": busy_s, "window_s": 20.0,
                     "by_module": by_module},
              platform=platform, device_kind=V5E,
              cell=NS(chips=chips, root=spec.REPO),
              work=work if work is not None else {})


def test_share_reads_the_exchange_modules_over_busy_time():
    w = _window({"jit__exchange_kernel": 1.5, "jit__exchange_probe": 0.5,
                 "jit_step_fn": 4.0, "jit__plan_kernel": 3.0})
    assert SHARE.read(w) == pytest.approx(20.0)


@pytest.mark.parametrize("w", [
    _window({"jit__exchange_kernel": 1.0}, platform="cpu"),
    _window({"jit_step_fn": 4.0}),               # no exchange ran
    _window({"jit__exchange_kernel": 1.0}, busy_s=0.0),
    NS(trace=None, platform="tpu"),              # an untraced run
])
def test_share_has_nothing_to_read(w):
    assert SHARE.read(w) is None


def test_exchange_bytes_divide_by_chips():
    work = {"heartbeats": 4_000_000, "game_updates": 4_000_000}
    assert rx.crossing(work, 4) == 3_000_000
    assert rx.bytes_per_chip(work, 4) == {
        "hbm": (24 * 3_000_000 + 4 * 4_000_000) / 4,
        "ici": 12 * 3_000_000 / 4}
    assert rx.crossing(work, 1) == 0


def test_roofline_is_one_chips_share_over_the_modules_time():
    work = {"heartbeats": 4e9, "game_updates": 4e9}
    w = _window({"jit__exchange_kernel": 2.0, "jit_step_fn": 9.0},
                work=work)
    # one chip's share of the crossing bytes, over the interconnect
    ici = 12 * 3e9 / 4 / 200e9
    assert ici > (24 * 3e9 + 4 * 4e9) / 4 / 819e9
    assert ROOFLINE.read(w) == pytest.approx(100.0 * ici / 2.0)
    # on one chip nothing crosses: only the classification's reads
    one = _window({"jit__exchange_kernel": 2.0}, work=work, chips=1)
    assert ROOFLINE.read(one) == pytest.approx(
        100.0 * 4 * 4e9 / 819e9 / 2.0)


def test_roofline_memory_bound():
    peaks = {"hbm_bytes_per_s": 1e9, "ici_bytes_per_s": 200e9}
    work = {"game_updates": 1e6}
    assert rx.least_seconds(work, 4, peaks) == pytest.approx(
        (24 * 0.75e6 + 4 * 1e6) / 4 / 1e9)


@pytest.mark.parametrize("w", [
    _window({"jit_step_fn": 1.0}, work={"game_updates": 1e6}),
    _window({"jit__exchange_kernel": 1.0}, platform="cpu"),
    NS(trace=None, platform="tpu"),
])
def test_roofline_has_nothing_to_read(w):
    assert ROOFLINE.read(w) is None


def test_interconnect_peak_known_and_unknown_device():
    assert rx.ici_peaks(V5E)["ici_bytes_per_s"] == 200e9
    with pytest.raises(KeyError, match="no interconnect peak"):
        rx.ici_peaks("TPU v0 imaginary")
