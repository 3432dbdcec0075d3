"""The fan-out expansion kernel against a plain ragged expansion.

``fanout._expand_kernel`` turns [m] source lanes into ``width`` output
slots: lane i's followers, in CSR order, fill the slots after every
earlier lane's, and a lane whose range does not end within ``width``
delivers nothing and is reported dropped.  The reference below walks
the lanes one by one in NumPy; every case compares ``dst``,
``out_valid``, ``total``, ``src_dropped`` and ``n_dropped`` exactly and
``src_index`` on the valid slots.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orleans_tpu.tensor import DeviceFanout
from orleans_tpu.tensor.fanout import KEY_SENTINEL, _expand_kernel


def build_csr(rows):
    """CSR arrays of ``rows`` ({src key: [dst, ...]}, empty lists kept as
    zero-degree rows), laid out as DeviceFanout lays them out: sorted
    keys, offsets, and dst padded with ``KEY_SENTINEL`` to a multiple of
    256.  No rows gives the sentinel row."""
    keys = sorted(rows)
    edges = sum(len(rows[k]) for k in keys)
    width = max(256, -(-max(1, edges) // 256) * 256)
    dst = np.full(width, KEY_SENTINEL, np.int32)
    if not keys:
        return (np.array([KEY_SENTINEL], np.int32), np.zeros(2, np.int32),
                dst)
    offsets = np.zeros(len(keys) + 1, np.int32)
    for i, k in enumerate(keys):
        d = rows[k]
        dst[offsets[i]:offsets[i] + len(d)] = d
        offsets[i + 1] = offsets[i] + len(d)
    return np.array(keys, np.int32), offsets, dst


def ragged_reference(rows, src_keys, valid, width):
    """(dst, src_index, out_valid, total, src_dropped, n_dropped), one
    lane at a time."""
    dst = np.full(width, KEY_SENTINEL, np.int32)
    src_index = np.zeros(width, np.int32)
    out_valid = np.zeros(width, bool)
    src_dropped = np.zeros(len(src_keys), bool)
    total = 0
    for i, (k, v) in enumerate(zip(src_keys.tolist(), valid.tolist())):
        followers = rows.get(k, []) if v else []
        lo, total = total, total + len(followers)
        if not followers:
            continue
        if total > width:
            src_dropped[i] = True
            continue
        dst[lo:total] = followers
        src_index[lo:total] = i
        out_valid[lo:total] = True
    return dst, src_index, out_valid, total, src_dropped, int(
        src_dropped.sum())


def assert_matches(got, want):
    dst, src_index, out_valid, total, src_dropped, n_dropped = (
        np.asarray(x) for x in got)
    w_dst, w_src, w_valid, w_total, w_dropped, w_n = want
    np.testing.assert_array_equal(dst, w_dst)
    np.testing.assert_array_equal(out_valid, w_valid)
    assert int(total) == w_total
    np.testing.assert_array_equal(src_dropped, w_dropped)
    assert int(n_dropped) == w_n
    np.testing.assert_array_equal(src_index[w_valid], w_src[w_valid])


def random_rows(rng, n_src, max_deg, zero_share=0.0):
    keys = rng.choice(10_000, n_src, replace=False)
    rows = {}
    for k in keys.tolist():
        deg = 0 if rng.random() < zero_share else int(
            rng.integers(1, max_deg + 1))
        rows[k] = rng.integers(0, 50_000, deg).tolist()
    return rows


def _case(name):
    """(rows, src_keys int32[m], valid bool[m], width or "csr")."""
    rng = np.random.default_rng(CASES.index(name))
    rows = {3: [30, 31, 32], 5: [], 8: [80], 9: [], 12: [120, 121],
            20: [200, 201, 202, 203]}
    ones = lambda keys: (np.array(keys, np.int32),  # noqa: E731
                         np.ones(len(keys), bool))
    if name == "zero_degree_runs":
        # runs of zero-degree lanes (CSR rows with no edges, and keys
        # the CSR lacks) between, before and after lanes with followers
        return (rows, *ones([5, 9, 3, 5, 9, 9, 8, 4, 5, 12, 9, 20]), 256)
    if name == "zero_degree_last_lane":
        return (rows, *ones([3, 12, 20, 9]), 256)
    if name == "masked_lanes":
        keys, _ = ones([3, 8, 12, 20, 3, 8])
        return rows, keys, np.array([1, 0, 1, 0, 0, 1], bool), 256
    if name == "duplicate_keys":
        return (rows, *ones([20, 3, 20, 20, 8, 8, 3]), 256)
    if name == "absent_keys":
        return (rows, *ones([1, 2, 3, 4, 10_000, 12, 77]), 256)
    if name == "single_lane":
        return (rows, *ones([20]), 256)
    if name == "single_lane_absent":
        return (rows, *ones([21]), 256)
    if name == "no_lanes":
        return (rows, *ones([]), 256)
    if name == "empty_csr":
        return ({}, *ones([1, 3, 5]), 256)
    if name == "width_below_total":
        # 3 + 1 + 2 + 4 + 3 = 13 deliveries into 8 slots: lanes past
        # slot 8 drop whole, including the one that would straddle it
        return (rows, *ones([3, 8, 9, 12, 20, 3]), 8)
    if name == "width_at_lane_end":
        # 3 + 1 + 2 = 6: the third lane ends on the last slot and fits
        return (rows, *ones([3, 8, 12, 20]), 6)
    if name == "width_below_total_zero_tail":
        return (rows, *ones([20, 3, 12, 5, 9]), 8)
    if name == "width_equals_csr":
        big = random_rows(rng, 300, 20, zero_share=0.2)
        keys = rng.choice(np.array(sorted(big)), 200).astype(np.int32)
        return big, keys, rng.random(200) < 0.9, "csr"
    if name == "random_width_256":
        big = random_rows(rng, 60, 12, zero_share=0.3)
        keys = rng.choice(np.array(sorted(big) + [10_001, 10_002]), 48)
        return big, keys.astype(np.int32), rng.random(48) < 0.8, 256
    if name == "random_rows_across_scan_rows":
        # a width over two scan rows of 1,024 that is not a multiple of
        # one, with lanes dropped past it
        big = random_rows(rng, 400, 30, zero_share=0.25)
        keys = rng.choice(np.array(sorted(big)), 300).astype(np.int32)
        return big, keys, rng.random(300) < 0.9, 2304
    raise KeyError(name)


CASES = ["zero_degree_runs", "zero_degree_last_lane", "masked_lanes",
         "duplicate_keys", "absent_keys", "single_lane",
         "single_lane_absent", "no_lanes", "empty_csr",
         "width_below_total", "width_at_lane_end",
         "width_below_total_zero_tail", "width_equals_csr",
         "random_width_256", "random_rows_across_scan_rows"]


@pytest.mark.parametrize("name", CASES)
def test_expand_kernel_matches_ragged_reference(name):
    rows, src_keys, valid, width = _case(name)
    ck, co, cd = build_csr(rows)
    if width == "csr":
        width = cd.shape[0]
    got = _expand_kernel(jnp.asarray(ck), jnp.asarray(co), jnp.asarray(cd),
                         jnp.asarray(src_keys), jnp.asarray(valid),
                         width=width)
    assert_matches(got, ragged_reference(rows, src_keys, valid, width))


def test_expand_kernel_drops_only_whole_lanes():
    """The straddling lane of ``width_below_total`` delivers none of its
    slots, and the slots that fit are exactly the earlier lanes'."""
    rows, src_keys, valid, width = _case("width_below_total")
    ck, co, cd = build_csr(rows)
    dst, _, out_valid, total, dropped, n_dropped = (np.asarray(x) for x in
        _expand_kernel(jnp.asarray(ck), jnp.asarray(co), jnp.asarray(cd),
                       jnp.asarray(src_keys), jnp.asarray(valid),
                       width=width))
    assert int(total) == 13 and int(n_dropped) == 2
    assert dropped.tolist() == [False, False, False, False, True, True]
    assert dst[out_valid].tolist() == [30, 31, 32, 80, 120, 121]


def test_expand_kernel_traced_in_outer_jit():
    """A fused window traces the kernel inside its own jit."""
    rows, src_keys, valid, width = _case("random_rows_across_scan_rows")
    ck, co, cd = (jnp.asarray(a) for a in build_csr(rows))

    @jax.jit
    def window(src, mask):
        return _expand_kernel(ck, co, cd, src, mask, width=width)

    got = window(jnp.asarray(src_keys), jnp.asarray(valid))
    assert_matches(got, ragged_reference(rows, src_keys, valid, width))


def test_device_fanout_sized_expand_matches_reference():
    """Through ``DeviceFanout.expand(..., keys_host=...)``: a round sized
    to its sources' degree sum gives the reference's slots, each with
    its own lane's args and source key."""
    rng = np.random.default_rng(11)
    rows = random_rows(rng, 200, 40)
    fan = DeviceFanout()
    for k, ds in rows.items():
        fan.add_edges(np.full(len(ds), k), np.array(ds))
    # add_edges stores each source's followers deduplicated and sorted
    rows = {k: fan.followers_of(k) for k in rows}
    keys = np.concatenate([rng.choice(np.array(sorted(rows)), 120),
                           [10_003, 10_004]]).astype(np.int32)
    lanes = jnp.arange(len(keys), dtype=jnp.int32)
    dst, gathered, out_valid = fan.expand(
        jnp.asarray(keys), {"lane": lanes}, keys_host=keys)
    assert int(fan.take_drop()[0]) == 0
    assert fan.sized_rounds == 1
    w_dst, w_src, w_valid, _, _, _ = ragged_reference(
        rows, keys, np.ones(len(keys), bool), fan.width)
    np.testing.assert_array_equal(np.asarray(dst), w_dst)
    np.testing.assert_array_equal(np.asarray(out_valid), w_valid)
    np.testing.assert_array_equal(np.asarray(gathered["lane"])[w_valid],
                                  w_src[w_valid])
    np.testing.assert_array_equal(np.asarray(gathered["src_key"])[w_valid],
                                  keys[w_src[w_valid]])
