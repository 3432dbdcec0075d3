"""The main path's kernels compile for a v5e chip at full width.

Nothing runs: each program is lowered against shapes placed on devices
of a described ``v5e:2x2`` topology and compiled by the TPU compiler, so
a kernel the chip would refuse (layout, memory, partitioning) fails here
at no chip time.  The topology is described inside a fixture, never at
import: only one process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

N_PLAYERS = 1 << 20      # PresenceGrain rows and heartbeat lanes
N_GAMES = 10_000         # GameGrain rows (presence-1m's games)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _widen(x, n, sharding):
    """``x``'s shape with its leading dim set to ``n`` (scalars stay)."""
    shape = (n,) + tuple(x.shape[1:]) if np.ndim(x) else ()
    return _spec(shape, x.dtype, sharding)


def test_presence_heartbeat_step(one_chip):
    from __graft_entry__ import entry

    fn, (state, rows, args, mask) = entry()
    state = {k: _widen(v, N_PLAYERS, one_chip) for k, v in state.items()}
    args = {k: _widen(v, N_PLAYERS, one_chip) for k, v in args.items()}
    compiled = jax.jit(fn).lower(
        state, _widen(rows, N_PLAYERS, one_chip), args,
        _widen(mask, N_PLAYERS, one_chip)).compile()
    assert compiled.memory_analysis().argument_size_in_bytes \
        >= 3 * 4 * N_PLAYERS


def test_game_update_step(one_chip):
    from orleans_tpu.tensor.vector_grain import Batch, vector_type
    import samples.presence  # noqa: F401 — registers the vector grains

    info = vector_type("GameGrain")
    handler = info.handlers["update_game_status"]
    state = {name: _spec((N_GAMES, *f.shape), f.dtype, one_chip)
             for name, f in info.state_fields.items()}
    lanes = N_PLAYERS  # one game update per heartbeat

    def fn(state, rows, args, mask):
        return handler(state, Batch(rows=rows, args=args, mask=mask),
                       N_GAMES)

    jax.jit(fn).lower(
        state, _spec((lanes,), jnp.int32, one_chip),
        {"score": _spec((lanes,), jnp.float32, one_chip),
         "count": _spec((lanes,), jnp.int32, one_chip)},
        _spec((lanes,), jnp.bool_, one_chip)).compile()


@pytest.mark.parametrize("kernel", ["dense", "sorted"])
def test_resolve_rows_kernels(one_chip, kernel):
    from orleans_tpu.tensor.engine import (
        _resolve_rows_dense_kernel,
        _resolve_rows_kernel,
    )

    i32 = _spec((N_PLAYERS,), jnp.int32, one_chip)
    valid = _spec((N_PLAYERS,), jnp.bool_, one_chip)
    if kernel == "dense":
        lowered = _resolve_rows_dense_kernel.lower(i32, i32, valid)
    else:
        lowered = _resolve_rows_kernel.lower(i32, i32, i32, valid)
    lowered.compile()


def test_miss_keys_kernel(one_chip):
    from orleans_tpu.tensor.engine import MISS_BUF, _miss_keys_kernel

    i32 = _spec((N_PLAYERS,), jnp.int32, one_chip)
    valid = _spec((N_PLAYERS,), jnp.bool_, one_chip)
    _miss_keys_kernel.lower(i32, i32, valid, miss_buf=MISS_BUF).compile()


def test_fanout_expand_kernel(one_chip):
    """chirper-100k's publish round: a 16,384-lane slab expanded into
    its 786,432-slot rung of a 3.5M-edge CSR over 100k accounts."""
    from orleans_tpu.tensor.fanout import _expand_kernel

    accounts, edges, lanes = 100_000, 3_500_032, 16_384
    i32 = lambda n: _spec((n,), jnp.int32, one_chip)  # noqa: E731
    _expand_kernel.lower(
        i32(accounts), i32(accounts + 1), i32(edges), i32(lanes),
        _spec((lanes,), jnp.bool_, one_chip), width=786_432).compile()


def test_exchange_all_to_all_on_four_chips(topo):
    """The structured exchange's per-shard program over a 4-chip mesh:
    Presence's game-update emits (1M lanes) bucketed by destination
    shard of the 10k-row game arena, moved with one all-to-all."""
    from types import SimpleNamespace

    from orleans_tpu.tensor.exchange import ShardExchange

    n = 4
    mesh = Mesh(np.array(topo.devices[:n]), ("grains",))
    # ShardExchange reads only these engine fields when it is built
    xch = ShardExchange(SimpleNamespace(
        mesh=mesh, n_shards=n, config=SimpleNamespace(mesh_axis="grains")))
    sharded = NamedSharding(mesh, PartitionSpec("grains"))
    L = N_PLAYERS // n
    shard_capacity = 16_384 // n   # the game arena's pow2 capacity
    cap = L // n                   # uniform traffic: L/n lanes per peer

    def fn(rows, mask, score, count):
        return xch._traced(rows, [score, count], mask, shard_capacity,
                           L, cap)

    compiled = jax.jit(fn).lower(
        _spec((N_PLAYERS,), jnp.int32, sharded),
        _spec((N_PLAYERS,), jnp.bool_, sharded),
        _spec((N_PLAYERS,), jnp.float32, sharded),
        _spec((N_PLAYERS,), jnp.int32, sharded)).compile()
    assert "all-to-all" in compiled.as_text()
