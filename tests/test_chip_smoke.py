"""chip_smoke.py on the CPU: its phases at a small size against the
NumPy replay, its refusal to run without a TPU, and the compile-cache
placement it (with the host) starts with."""

import asyncio
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import chip_smoke
from orleans_tpu.utils.compile_cache import REPO_CACHE_DIR, \
    enable_compile_cache

REPO = Path(__file__).resolve().parent.parent


def _reporter(lines):
    return chip_smoke.Reporter(require_memory_stats=False, out=lines.append)


def test_single_chip_phases_match_replay():
    lines = []
    rep = _reporter(lines)
    asyncio.run(chip_smoke.run_single_chip(10_000, 100, 3, rep))
    phases = [p["phase"] for p in rep.lines]
    assert phases == ["silo_boot", "hello", "cold_activate", "unfused",
                      "fused", "autofused", "client_heartbeat"]
    by = {p["phase"]: p for p in rep.lines}
    assert by["fused"]["misses"] == 0
    assert by["autofused"]["ticks_fused"] > 0
    assert all(p["float_err_of_bound"] <= 1.0 for p in rep.lines
               if "float_err_of_bound" in p)
    assert len(lines) == len(phases)


def test_mesh_phase_matches_one_device():
    """The --chips 4 phase on 4 of the virtual CPU devices; the
    structured exchange is forced on (auto keeps it off on the CPU)."""
    rep = _reporter([])
    asyncio.run(chip_smoke.run_mesh(2_000, 20, 5, 4, rep,
                                    structured="always"))
    mesh = rep.lines[-1]
    assert mesh["phase"] == "mesh" and mesh["cross_shard_msgs"] > 0
    live = json.loads(mesh["live_rows_per_device"])
    assert all(len(v) == 4 and min(v) > 0 for v in live.values())


def test_refuses_to_run_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.fixture
def cache_dir_config():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_left_to_jax_when_placed(monkeypatch, tmp_path,
                                               cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo_dir(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert first == str(REPO / ".jax_cache") == REPO_CACHE_DIR
    assert enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
