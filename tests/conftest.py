"""Test configuration: force a virtual 8-device CPU mesh before jax loads.

Mirrors the reference's test strategy of simulating a multi-silo cluster in
one process (reference: src/OrleansTestingHost/TestingSiloHost.cs:58 —
AppDomain-per-silo); here multi-*device* is simulated with XLA's host
platform device count, and multi-*silo* with multiple Silo objects on one
event loop (see orleans_tpu/testing).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import asyncio  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def run():
    """Run a coroutine to completion on a fresh event loop."""

    def _run(coro):
        return asyncio.run(coro)

    return _run
