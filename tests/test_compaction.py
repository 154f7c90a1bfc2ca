"""Property tests for stream compaction (the pshufb replacement)."""

import numpy as np
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import compaction

SETTINGS = dict(max_examples=50, deadline=None)


@settings(**SETTINGS)
@given(st.lists(st.tuples(st.integers(-100, 100), st.booleans()),
                min_size=1, max_size=64))
def test_compact_matches_numpy(items):
    vals = np.array([v for v, _ in items], np.int32)
    mask = np.array([m for _, m in items], bool)
    out, cnt = compaction.compact(jnp.asarray(vals), jnp.asarray(mask),
                                  len(vals))
    want = vals[mask]
    assert int(cnt) == len(want)
    assert np.array_equal(np.asarray(out)[: len(want)], want)


@settings(**SETTINGS)
@given(st.lists(st.tuples(st.integers(-100, 100), st.booleans()),
                min_size=1, max_size=64))
def test_compact_gather_matches_scatter(items):
    vals = np.array([v for v, _ in items], np.int32)
    mask = np.array([m for _, m in items], bool)
    o1, c1 = compaction.compact(jnp.asarray(vals), jnp.asarray(mask),
                                len(vals))
    o2, c2 = compaction.compact_gather(jnp.asarray(vals), jnp.asarray(mask),
                                       len(vals))
    assert int(c1) == int(c2)
    assert np.array_equal(np.asarray(o1)[: int(c1)],
                          np.asarray(o2)[: int(c2)])


# ---------------------------------------------------------------------------
# Directed edge cases: empty inputs, degenerate masks, exact-capacity and
# overflow buffers, the tile-scan helper.


def test_compact_all_false_mask():
    vals = jnp.arange(16, dtype=jnp.int32)
    out, cnt = compaction.compact(vals, jnp.zeros(16, bool), 16, fill=-7)
    assert int(cnt) == 0
    assert np.all(np.asarray(out) == -7)
    out, tot = compaction.compact_offsets(
        jnp.ones((16, 4), jnp.int32), jnp.full(16, 3, jnp.int32),
        jnp.zeros(16, bool), 8)
    assert int(tot) == 0


def test_compact_zero_length_input():
    out, cnt = compaction.compact(
        jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool), 4)
    assert int(cnt) == 0 and out.shape == (4,)
    out, tot = compaction.compact_offsets(
        jnp.zeros((0, 4), jnp.int32), jnp.zeros((0,), jnp.int32),
        jnp.zeros((0,), bool), 4)
    assert int(tot) == 0 and out.shape == (4,)


def test_compact_offsets_exact_capacity():
    vals = jnp.arange(12, dtype=jnp.int32).reshape(6, 2)
    lens = jnp.full(6, 2, jnp.int32)
    mask = jnp.ones(6, bool)
    out, tot = compaction.compact_offsets(vals, lens, mask, 12)
    assert int(tot) == 12
    assert np.array_equal(np.asarray(out), np.arange(12))


def test_compact_offsets_overflow_drops_tail():
    vals = jnp.arange(12, dtype=jnp.int32).reshape(6, 2)
    lens = jnp.full(6, 2, jnp.int32)
    mask = jnp.ones(6, bool)
    out, tot = compaction.compact_offsets(vals, lens, mask, 5)
    assert int(tot) == 12  # logical total; buffer truncates physically
    assert np.array_equal(np.asarray(out), np.arange(5))


def test_compact_overflow_drops_tail():
    vals = jnp.arange(10, dtype=jnp.int32)
    out, cnt = compaction.compact(vals, jnp.ones(10, bool), 4)
    assert int(cnt) == 10
    assert np.array_equal(np.asarray(out), np.arange(4))


def test_tile_exclusive_scan_matches_numpy():
    rng = np.random.default_rng(3)
    x = rng.integers(0, 5, 1024).astype(np.int32)
    excl, tot = compaction.tile_exclusive_scan(jnp.asarray(x), rows=8)
    want = np.cumsum(x) - x
    assert np.array_equal(np.asarray(excl), want)
    assert int(tot) == int(x.sum())
    # ragged row width + all-zero tile
    x = np.zeros(256, np.int32)
    excl, tot = compaction.tile_exclusive_scan(jnp.asarray(x), rows=4)
    assert int(tot) == 0 and np.all(np.asarray(excl) == 0)


@settings(**SETTINGS)
@given(st.lists(st.tuples(st.integers(0, 255), st.integers(0, 4),
                          st.booleans()),
                min_size=1, max_size=48))
def test_compact_offsets_matches_numpy(items):
    n = len(items)
    k = 4
    vals = np.zeros((n, k), np.int32)
    lens = np.array([l for _, l, _ in items], np.int32)
    mask = np.array([m for _, _, m in items], bool)
    rng = np.random.default_rng(0)
    for i, (v, l, _) in enumerate(items):
        vals[i, :] = rng.integers(0, 256, k)
    cap = int((lens * mask).sum()) + 8
    out, total = compaction.compact_offsets(
        jnp.asarray(vals), jnp.asarray(lens), jnp.asarray(mask), cap)
    want = []
    for i in range(n):
        if mask[i]:
            want.extend(vals[i, : lens[i]])
    assert int(total) == len(want)
    assert np.array_equal(np.asarray(out)[: len(want)],
                          np.array(want, np.int32))
