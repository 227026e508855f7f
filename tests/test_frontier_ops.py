"""The frontier helpers that stand in for ``jnp.cumsum``, ``jnp.nonzero``
and a plain ``.at[]`` scatter on the main path give those operations'
exact results, on both sides of the lengths where they change method
(the 1024-entry scan block, the 2**21-entry scatter chunk); the LB
pass's scan stands in for ``searchsorted``, and masked gather slots
keep in range."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import frontier

CHUNK = frontier._SCATTER_CHUNK


@pytest.mark.parametrize("n", [0, 1, 1023, 1024, 1025, 5 * 1024 + 3,
                               1024 * 1024 + 17])
def test_prefix_sum_matches_cumsum(n):
    x = np.random.default_rng(n).integers(0, 9, n).astype(np.int32)
    got = np.asarray(frontier.prefix_sum(jnp.asarray(x)))
    np.testing.assert_array_equal(got, np.cumsum(x, dtype=np.int32))


@pytest.mark.parametrize("v,size", [(100, 64), (5000, 8192),
                                    (CHUNK + 4000, 1 << 12)])
def test_compact_matches_nonzero(v, size):
    mask = np.random.default_rng(v).random(v) < 0.001
    want = np.full(size, v, np.int32)
    hits = np.flatnonzero(mask)[:size]
    want[:len(hits)] = hits
    got = np.asarray(frontier.compact(jnp.asarray(mask), size))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("combine", ["min", "add", "set"])
@pytest.mark.parametrize("b,v", [(1, 1000), (1, 2 * CHUNK + 300),
                                 (2, CHUNK + 300)])
def test_scatter_rows_matches_plain_scatter(combine, b, v):
    rng = np.random.default_rng(v + b)
    n = 5000
    if combine == "set":                   # unique targets: one winner
        idx = rng.permutation(v + 50)[:n]
    else:                                  # duplicates, chunk edges
        idx = np.concatenate([rng.integers(0, v + 50, n - 6),
                              [0, CHUNK - 1, CHUNK, v - 1, v, v + 7]])
    idx = idx.astype(np.int32)             # >= v: out of range, dropped
    rows = rng.integers(0, 100, (b, v)).astype(np.int32)
    upd = rng.integers(0, 100, (b, len(idx))).astype(np.int32)
    want = rows.copy()
    keep = idx < v
    for r in range(b):
        if combine == "min":
            np.minimum.at(want[r], idx[keep], upd[r, keep])
        elif combine == "add":
            np.add.at(want[r], idx[keep], upd[r, keep])
        else:
            want[r, idx[keep]] = upd[r, keep]
    got = frontier.scatter_rows(jnp.asarray(rows), jnp.asarray(idx),
                                jnp.asarray(upd), combine)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("n", [1, 2, 1000, 1 << 20])
def test_spread_index_keeps_kept_and_stays_in_range(n):
    rng = np.random.default_rng(n)
    idx = rng.integers(0, n, (300, 8)).astype(np.int32)
    keep = rng.random((300, 8)) < 0.3
    got = np.asarray(frontier.spread_index(jnp.asarray(keep),
                                           jnp.asarray(idx), n))
    np.testing.assert_array_equal(got[keep], idx[keep])
    assert got.min() >= 0 and got.max() < n
    if n >= 1000:                          # the masked reads are spread
        assert len(np.unique(got[~keep])) > 100


@pytest.mark.parametrize("n", [64, CHUNK + 100])
def test_segment_values_matches_searchsorted(n):
    from repro.core.balancer import _segment_values
    rng = np.random.default_rng(n)
    deg = rng.integers(0, 40, 700)
    deg[[0, 5, 6, 699]] = 0                # zero-length slots, both ends
    deg[10] = n                            # a slot running past n
    starts = (np.cumsum(deg) - deg).astype(np.int32)
    vals = rng.integers(-1 << 30, 1 << 30, 700).astype(np.int32)
    ids = np.arange(n)
    want = vals[np.searchsorted(starts, ids, side="right") - 1]
    got = _segment_values(jnp.asarray(starts), jnp.asarray(vals), n)
    np.testing.assert_array_equal(np.asarray(got), want)
