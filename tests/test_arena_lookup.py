"""Host key→row lookup: the dense mirror answers exactly as the binary
search over the sorted mirror does.

``GrainArena.lookup_rows`` gathers from a dense key→row array when the
arena's keys are non-negative, below ``DENSE_KEY_BOUND`` and occupy
enough of their range; otherwise it binary-searches the sorted mirror.
Each case builds an arena, then compares the dense answer with the sorted
answer on the same arena and with the row map itself, for every key
queried.  The fallback cases check that the sorted path still engages.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from orleans_tpu.core.grain import batched_method
from orleans_tpu.tensor import (
    Batch,
    TensorEngine,
    VectorGrain,
    field,
    seg_sum,
    vector_grain,
)
from orleans_tpu.tensor.arena import GrainArena
from orleans_tpu.tensor.vector_grain import vector_type


@vector_grain
class LookupProbeGrain(VectorGrain):
    hits = field(jnp.int32, 0)

    @batched_method
    @staticmethod
    def hit(state, batch: Batch, n_rows: int):
        ones = jnp.ones_like(batch.rows, dtype=jnp.int32) * batch.mask
        return {**state, "hits": state["hits"]
                + seg_sum(ones, batch.rows, n_rows)}, None, ()


def _arena(capacity=256, n_shards=1) -> GrainArena:
    return GrainArena(vector_type("LookupProbeGrain"), capacity=capacity,
                      n_shards=n_shards)


def _truth(arena: GrainArena, keys: np.ndarray):
    """key → primary row from the row map itself (replica secondaries
    carry their key but are never a lookup's answer)."""
    live = (arena._key_of_row >= 0) & ~arena._replica_secondary
    rows = np.nonzero(live)[0]
    where = dict(zip(arena._key_of_row[rows].tolist(), rows.tolist()))
    want = np.array([where.get(int(k), -1) for k in keys], np.int32)
    return want, want >= 0


def _sorted_answer(arena: GrainArena, keys: np.ndarray):
    """The same arena's answer with the dense mirror set aside."""
    dense = arena._dense
    arena._dense = None
    try:
        return arena.lookup_rows(keys)
    finally:
        arena._dense = dense


def _absent():
    a = _arena()
    a.resolve_rows(np.arange(0, 200, 2, dtype=np.int64))
    return a, np.arange(0, 200, dtype=np.int64), True


def _negative():
    a = _arena()
    a.resolve_rows(np.arange(100, dtype=np.int64))
    q = np.array([-1, -2, -99, -(2**40), 0, 5, 99], dtype=np.int64)
    return a, q, True


def _past_largest():
    a = _arena()
    a.resolve_rows(np.arange(100, dtype=np.int64))
    a.lookup_rows(np.zeros(1, np.int64))  # builds the mirror
    n = len(a._dense)
    q = np.array([99, 100, 101, n - 1, n, n + 5, 2**31, 2**40, 2**62],
                 dtype=np.int64)
    return a, q, True


def _after_eviction():
    a = _arena()
    a.resolve_rows(np.arange(300, dtype=np.int64))
    assert a.evict_keys(np.arange(0, 300, 3, dtype=np.int64),
                        write_back=False) == 100
    return a, np.arange(-5, 310, dtype=np.int64), True


def _after_growth():
    a = _arena(capacity=8)
    a.resolve_rows(np.arange(0, 40, 5, dtype=np.int64))
    a.lookup_rows(np.arange(40, dtype=np.int64))
    gen = a.generation
    a.resolve_rows(np.arange(1000, dtype=np.int64))  # grows: rows move
    assert a.generation > gen
    return a, np.arange(-3, 1100, dtype=np.int64), True


def _after_migration_pin():
    a = _arena(capacity=128, n_shards=2)
    keys = np.arange(200, dtype=np.int64)
    rows = a.resolve_rows(keys)
    movers = keys[:40]
    dst = 1 - rows[:40] // a.shard_capacity
    assert a.migrate_keys(movers, dst, pin=True) == 40
    assert a._shard_override
    return a, np.arange(-2, 210, dtype=np.int64), True


def _with_replica_secondaries():
    a = _arena(capacity=128, n_shards=2)
    a.resolve_rows(np.arange(150, dtype=np.int64))
    assert a.promote_replicas(7, 3) == 3
    assert a.promote_replicas(42, 2) == 2
    assert a._replica_secondary.sum() == 3
    return a, np.arange(160, dtype=np.int64), True


def _sparse_past_guard():
    a = _arena()
    a.resolve_rows(np.array([0, 3, 9, 500_000], dtype=np.int64))
    return a, np.array([-1, 0, 1, 3, 9, 500_000, 500_001], np.int64), False


def _wide_keys():
    a = _arena()
    a.resolve_rows(np.array([1, 2**40 + 1, 2**40 + 2], dtype=np.int64))
    q = np.array([1, 2, 2**40, 2**40 + 1, 2**40 + 2, -1], dtype=np.int64)
    return a, q, False


CASES = {
    "absent": _absent,
    "negative": _negative,
    "past_largest": _past_largest,
    "after_eviction": _after_eviction,
    "after_growth": _after_growth,
    "after_migration_pin": _after_migration_pin,
    "replica_secondaries": _with_replica_secondaries,
    "sparse_past_guard": _sparse_past_guard,
    "wide_keys": _wide_keys,
}


@pytest.mark.parametrize("case", list(CASES))
def test_lookup_matches_sorted_answer(case):
    arena, keys, dense = CASES[case]()
    d0, s0 = arena.dense_lookup_keys, arena.sorted_lookup_keys
    rows, found = arena.lookup_rows(keys)
    assert rows.dtype == np.int32 and found.dtype == bool
    if dense:
        assert arena._dense is not None
        assert arena.dense_lookup_keys - d0 == len(keys)
        assert arena.sorted_lookup_keys == s0
    else:
        # the fallback: the binary search engages, the mirror does not
        assert arena._dense is None
        assert arena.sorted_lookup_keys - s0 == len(keys)
        assert arena.dense_lookup_keys == d0
    want_rows, want_found = _sorted_answer(arena, keys)
    np.testing.assert_array_equal(rows, want_rows)
    np.testing.assert_array_equal(found, want_found)
    truth_rows, truth_found = _truth(arena, keys)
    np.testing.assert_array_equal(rows, truth_rows)
    np.testing.assert_array_equal(found, truth_found)


def test_dense_index_uploads_the_host_mirror():
    arena = _arena()
    arena.resolve_rows(np.arange(0, 3000, 3, dtype=np.int64))
    dev = arena.dense_index()
    assert dev is not None
    np.testing.assert_array_equal(np.asarray(dev), arena._dense)
    # a rebuild after eviction re-uploads the new contents
    arena.evict_keys(np.array([0, 3, 6], dtype=np.int64), write_back=False)
    dev = arena.dense_index()
    np.testing.assert_array_equal(np.asarray(dev), arena._dense)
    assert (arena._dense[[0, 3, 6]] == -1).all()
    # no mirror, no upload
    sparse = _arena()
    sparse.resolve_rows(np.array([1, 7_000_000], dtype=np.int64))
    assert sparse.dense_index() is None and sparse._dense is None


def test_snapshot_publishes_lookup_counters(run):
    async def main():
        engine = TensorEngine()
        arena = engine.arena_for("LookupProbeGrain")
        keys = np.arange(512, dtype=np.int64)
        arena.resolve_rows(keys)
        before = engine.snapshot()["lookup_keys"]["LookupProbeGrain"]
        n = 300
        engine.send_batch("LookupProbeGrain", "hit", keys[:n][::-1].copy(),
                          {})
        await engine.flush()
        after = engine.snapshot()["lookup_keys"]["LookupProbeGrain"]
        assert after["dense_lookup_keys"] - before["dense_lookup_keys"] == n
        assert after["sorted_lookup_keys"] == before["sorted_lookup_keys"]
        assert int(arena.read_row(7)["hits"]) == 1

    run(main())
