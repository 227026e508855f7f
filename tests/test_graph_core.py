"""Graph container + generator invariants."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import graph as G


def test_csr_from_edge_list_roundtrip():
    src = np.array([0, 0, 1, 3, 3, 3])
    dst = np.array([1, 2, 2, 0, 1, 2])
    g = G.from_edge_list(src, dst, 4)
    assert g.num_vertices == 4
    assert g.num_edges == 6
    np.testing.assert_array_equal(np.asarray(g.row_ptr), [0, 2, 3, 3, 6])
    np.testing.assert_array_equal(np.asarray(g.out_degrees()), [2, 1, 0, 3])


def test_from_edge_list_dedup():
    g = G.from_edge_list(np.array([0, 0, 0]), np.array([1, 1, 2]), 3)
    assert g.num_edges == 2


def test_rmat_power_law():
    g = G.rmat(10, 8, seed=0)
    assert g.num_vertices == 1024
    deg = np.asarray(g.out_degrees())
    # power-law: max degree far above mean
    assert deg.max() > 10 * deg.mean()
    assert int(deg.sum()) == g.num_edges


def test_road_grid_flat_degree():
    g = G.road_grid(16)
    deg = np.asarray(g.out_degrees())
    assert deg.max() <= 4
    assert g.num_vertices == 256


def test_uniform_balanced():
    g = G.uniform_random(1024, 8, seed=0)
    deg = np.asarray(g.out_degrees())
    assert deg.max() < 8 * deg.mean()


def test_reverse_graph_preserves_edges():
    g = G.rmat(8, 4, seed=1)
    rg = G.reverse_graph(g)
    assert rg.num_edges == g.num_edges
    # reversing twice restores the out-degree multiset
    rrg = G.reverse_graph(rg)
    np.testing.assert_array_equal(
        np.sort(np.asarray(rrg.out_degrees())),
        np.sort(np.asarray(g.out_degrees())))


def test_pad_graph_alignment_and_semantics():
    g = G.rmat(7, 3, seed=2)
    gp = G.pad_graph(g, v_multiple=8, e_multiple=1024)
    assert gp.num_vertices % 8 == 0
    assert gp.num_edges % 1024 == 0
    # padded vertices have degree 0
    deg = np.asarray(gp.out_degrees())
    assert (deg[g.num_vertices:] == 0).all()
    # real structure unchanged
    np.testing.assert_array_equal(np.asarray(gp.row_ptr[: g.num_vertices + 1]),
                                  np.asarray(g.row_ptr))


def test_pad_graph_padded_edges_target_padded_vertex():
    """Regression: with V already aligned but E padded, padded col_idx
    entries used to point at the REAL vertex V-1 — a weight-ignoring
    operator walking the padded edge span would corrupt its label.
    Padded edges must target a padded (degree-0, never-read) vertex."""
    g = G.rmat(7, 3, seed=2)          # V=128 is a multiple of 8
    assert g.num_vertices % 8 == 0 and g.num_edges % 1024 != 0
    gp = G.pad_graph(g, v_multiple=8, e_multiple=1024)
    assert gp.num_vertices > g.num_vertices    # vp forced past V
    padded_dst = np.asarray(gp.col_idx[g.num_edges:])
    assert (padded_dst >= g.num_vertices).all()
    assert (padded_dst < gp.num_vertices).all()


def test_pad_graph_cc_edge_lb_unharmed():
    """cc (weight-ignoring, min-combine) via the edge-balanced path on
    an aligned-V / padded-E graph must leave real labels identical to
    the unpadded run — the satellite regression for the padded-edge
    target fix."""
    from repro.core.apps import cc
    from repro.core.balancer import BalancerConfig
    g = G.symmetrized(G.rmat(7, 3, seed=2))
    assert g.num_vertices % 8 == 0
    gp = G.pad_graph(g, v_multiple=8, e_multiple=1024)
    assert gp.num_edges > g.num_edges
    cfg = BalancerConfig(strategy="edge_lb", threshold=64)
    ref = cc(g, cfg)
    for mode in ["host", "spmd"]:
        out = cc(gp, cfg, mode=mode)
        np.testing.assert_array_equal(
            np.asarray(out.labels[: g.num_vertices]),
            np.asarray(ref.labels), err_msg=mode)


def test_symmetrized_preserves_weights():
    """Regression: symmetrized() used to drop weights, silently turning
    weighted SSSP on symmetrized inputs into unit-weight BFS."""
    src = np.array([0, 1, 2, 0])
    dst = np.array([1, 2, 0, 2])
    w = np.array([7, 3, 9, 5])
    g = G.from_edge_list(src, dst, 3, weights=w)
    sg = G.symmetrized(g)
    ssrc, sdst, sw = G.to_coo(sg)
    wmap = {(int(a), int(b)): int(x) for a, b, x in zip(ssrc, sdst, sw)}
    # both directions exist and carry the min over duplicates
    assert wmap[(0, 1)] == wmap[(1, 0)] == 7
    assert wmap[(1, 2)] == wmap[(2, 1)] == 3
    # (0,2)/(2,0): forward weight 5, reverse of (2,0) weight 9 -> min 5
    assert wmap[(0, 2)] == wmap[(2, 0)] == 5
    # round-trip: symmetrizing a symmetric graph is the identity
    s2 = G.symmetrized(sg)
    np.testing.assert_array_equal(np.asarray(s2.row_ptr),
                                  np.asarray(sg.row_ptr))
    np.testing.assert_array_equal(np.asarray(s2.col_idx),
                                  np.asarray(sg.col_idx))
    np.testing.assert_array_equal(np.asarray(s2.edge_w),
                                  np.asarray(sg.edge_w))


def test_from_edge_list_dedup_keeps_min_weight():
    """Regression: dedup used to keep an input-order-dependent
    duplicate's weight; it must keep the per-(src, dst) minimum,
    independent of edge order."""
    src = np.array([0, 0, 0, 0])
    dst = np.array([1, 1, 1, 2])
    w = np.array([9, 2, 5, 4])
    g = G.from_edge_list(src, dst, 3, weights=w)
    assert g.num_edges == 2
    np.testing.assert_array_equal(np.asarray(g.edge_w), [2, 4])
    # permuting the input edges changes nothing
    perm = np.array([2, 3, 0, 1])
    g2 = G.from_edge_list(src[perm], dst[perm], 3, weights=w[perm])
    np.testing.assert_array_equal(np.asarray(g.col_idx),
                                  np.asarray(g2.col_idx))
    np.testing.assert_array_equal(np.asarray(g.edge_w),
                                  np.asarray(g2.edge_w))


def test_highest_out_degree_vertex():
    g = G.rmat(8, 8, seed=0)
    v = G.highest_out_degree_vertex(g)
    deg = np.asarray(g.out_degrees())
    assert deg[v] == deg.max()


# ---------------------------------------------------------------------------
# Versioned memoization (DESIGN.md section 10).
# ---------------------------------------------------------------------------

def test_reverse_cache_invalidated_by_version_bump():
    """Regression: ``reverse()`` used to memoize with no invalidation
    hook, so an in-place topology change kept serving the OLD
    transpose.  The cache is now keyed on ``Graph.version``."""
    from repro.core import streaming as S
    g = S.streaming_graph(G.rmat(5, 4, seed=2))
    rg_before = g.reverse()
    assert g.reverse() is rg_before            # memoized while static
    far = int(np.argmax(np.asarray(g.col_idx)[:1]))  # any real vertex
    S.apply_updates(g, S.make_batch([("insert", 0, 1, 7)]),
                    in_place=True)
    rg_after = g.reverse()
    assert rg_after is not rg_before
    # the new transpose must contain the inserted edge reversed
    em = S.edge_map(rg_after)
    assert em.get((1, 0)) == 7 or (1, 0) in em


def test_pull_after_mutation_matches_push():
    """Regression for the stale ``_pull_enum`` hazard: a pull-direction
    run AFTER an in-place mutation must agree with push on the mutated
    graph (it used to traverse the pre-mutation enumeration)."""
    from repro.core import streaming as S
    from repro.core.balancer import BalancerConfig
    from repro.core.apps import drivers

    g = S.streaming_graph(G.rmat(5, 4, seed=2))
    push = BalancerConfig(strategy="alb", threshold=64,
                          direction="push")
    pull = BalancerConfig(strategy="alb", threshold=64,
                          direction="pull")
    # populate both the reverse() and _pull_enum caches pre-mutation
    drivers.bfs(g, 0, pull)
    # mutate in place: add a shortcut that changes bfs levels
    lab0 = np.asarray(drivers.bfs(g, 0, push).labels)
    far = int(np.argmax(lab0[: S.real_vertices(g)]))
    S.apply_updates(g, S.make_batch([("insert", 0, far, 1)]),
                    in_place=True)
    got_pull = np.asarray(drivers.bfs(g, 0, pull).labels)
    got_push = np.asarray(drivers.bfs(g, 0, push).labels)
    nv = S.real_vertices(g)
    np.testing.assert_array_equal(got_pull[:nv], got_push[:nv])
    assert got_pull[far] == 1                  # the mutation took


def test_version_starts_at_zero_and_bumps():
    g = G.rmat(4, 4, seed=0)
    assert g.version == 0
    g.bump_version()
    assert g.version == 1
    # pytree round-trips never carry the version (it lives outside
    # the flattened leaves, so jit cache keys are unaffected)
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(g)
    g2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert g2.version == 0


def _csr_reference(src, dst, n, weights=None, dedup=True):
    """The CSR build as it was before the set-up speed-up: a (key,
    weight) lexsort for the weighted dedup, then a (src, dst) lexsort
    of the survivors.  Kept as the oracle the faster build must match
    bitwise."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if dedup and len(src):
        key = src * np.int64(n) + dst
        if weights is None:
            _, keep = np.unique(key, return_index=True)
            src, dst = src[keep], dst[keep]
        else:
            weights = np.asarray(weights)
            by_w = np.lexsort((weights, key))
            key, src, dst, weights = (key[by_w], src[by_w], dst[by_w],
                                      weights[by_w])
            keep = np.concatenate([[True], key[1:] != key[:-1]])
            src, dst, weights = src[keep], dst[keep], weights[keep]
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    if weights is None:
        weights = np.ones(len(src), dtype=np.int32)
    else:
        weights = np.asarray(weights, dtype=np.int32)[order]
    row_ptr = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(np.bincount(src, minlength=n), out=row_ptr[1:])
    return row_ptr, dst.astype(np.int32), weights


def _rmat_edges_reference(scale, edge_factor, seed, max_weight=100):
    """The RMAT quadrant draw as it was written with ``np.select``."""
    rng = np.random.default_rng(seed)
    m = edge_factor << scale
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for _ in range(scale):
        r = rng.random(m)
        quad = np.select([r < 0.57, r < 0.76, r < 0.95], [0, 1, 2],
                         default=3)
        src = (src << 1) | (quad >= 2)
        dst = (dst << 1) | (quad & 1)
    return src, dst, rng.integers(1, max_weight + 1, size=m)


def _assert_csr(g, want):
    for got, ref in zip((g.row_ptr, g.col_idx, g.edge_w), want):
        np.testing.assert_array_equal(np.asarray(got), ref)
        assert np.asarray(got).dtype == ref.dtype


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_rmat_matches_reference_build(seed):
    src, dst, w = _rmat_edges_reference(9, 16, seed)
    _assert_csr(G.rmat(9, 16, seed=seed), _csr_reference(src, dst, 512, w))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("weighted", [True, False])
@pytest.mark.parametrize("dedup", [True, False])
def test_from_edge_list_matches_reference_build(seed, weighted, dedup):
    """Random multigraphs with many parallel edges and self-loops."""
    rng = np.random.default_rng(seed)
    n = 97
    src = rng.integers(0, n, 3000)
    dst = rng.integers(0, n, 3000)
    w = rng.integers(-5, 50, 3000) if weighted else None
    _assert_csr(G.from_edge_list(src, dst, n, weights=w, dedup=dedup),
                _csr_reference(src, dst, n, w, dedup))
