"""All load-balancing strategies must compute identical fixpoints.

numpy references implement each app independently (Bellman-Ford /
BFS levels / label propagation / iterative peel / power iteration).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import graph as G
from repro.core.balancer import BalancerConfig, relax, relax_spmd
from repro.core.frontier import single_source
from repro.core import operators as ops
from repro.core.apps import bfs, sssp, cc, pagerank, kcore

STRATS = ["vertex", "twc", "edge_lb", "alb"]


# ---------------- numpy oracles ----------------

def np_csr(g):
    rp = np.asarray(g.row_ptr).astype(np.int64)
    ci = np.asarray(g.col_idx).astype(np.int64)
    w = np.asarray(g.edge_w).astype(np.int64)
    src = np.repeat(np.arange(g.num_vertices), rp[1:] - rp[:-1])
    return rp, ci, w, src


def np_sssp(g, source):
    rp, ci, w, src = np_csr(g)
    dist = np.full(g.num_vertices, int(G.INF), np.int64)
    dist[source] = 0
    for _ in range(g.num_vertices):
        new = dist.copy()
        np.minimum.at(new, ci, dist[src] + w)
        if np.array_equal(new, dist):
            break
        dist = new
    return dist


def np_bfs(g, source):
    rp, ci, w, src = np_csr(g)
    lvl = np.full(g.num_vertices, int(G.INF), np.int64)
    lvl[source] = 0
    for _ in range(g.num_vertices):
        new = lvl.copy()
        np.minimum.at(new, ci, lvl[src] + 1)
        if np.array_equal(new, lvl):
            break
        lvl = new
    return lvl


def np_cc(g):
    rp, ci, w, src = np_csr(g)
    comp = np.arange(g.num_vertices)
    for _ in range(g.num_vertices):
        new = comp.copy()
        np.minimum.at(new, ci, comp[src])
        if np.array_equal(new, comp):
            break
        comp = new
    return comp


def np_kcore(g, k):
    rp, ci, w, src = np_csr(g)
    deg = (rp[1:] - rp[:-1]).copy()
    alive = np.ones(g.num_vertices, bool)
    changed = True
    while changed:
        dead = alive & (deg < k)
        changed = bool(dead.any())
        for v in np.nonzero(dead)[0]:
            alive[v] = False
            deg[ci[rp[v]:rp[v + 1]]] -= 1
    return alive.astype(np.int32)


def np_pagerank(g, damping=0.85, iters=30):
    """Power iteration with dangling (out-degree 0) mass redistributed
    uniformly each round, so sum(rank) == 1 on graphs with sinks."""
    rp, ci, w, src = np_csr(g)
    n = g.num_vertices
    outdeg = rp[1:] - rp[:-1]
    inv = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1), 0.0)
    rank = np.full(n, 1.0 / n)
    for _ in range(iters):
        acc = np.zeros(n)
        np.add.at(acc, ci, rank[src] * inv[src])
        dangling = rank[outdeg == 0].sum()
        rank = (1 - damping) / n + damping * (acc + dangling / n)
    return rank


def symmetrize(g):
    rp, ci, w, src = np_csr(g)
    return G.from_edge_list(np.concatenate([src, ci]),
                            np.concatenate([ci, src]), g.num_vertices)


# ---------------- fixtures ----------------

@pytest.fixture(scope="module", params=["rmat", "road", "uniform"])
def graph(request):
    if request.param == "rmat":
        return G.rmat(9, 8, seed=3)
    if request.param == "road":
        return G.road_grid(20, seed=3)
    return G.uniform_random(512, 6, seed=3)


# ---------------- tests ----------------

@pytest.mark.parametrize("strategy", STRATS)
def test_sssp_all_strategies(graph, strategy):
    src = G.highest_out_degree_vertex(graph)
    cfg = BalancerConfig(strategy=strategy, threshold=64)
    out = sssp(graph, src, cfg)
    np.testing.assert_array_equal(np.asarray(out.labels), np_sssp(graph, src))


@pytest.mark.parametrize("strategy", STRATS)
def test_bfs_all_strategies(graph, strategy):
    src = G.highest_out_degree_vertex(graph)
    cfg = BalancerConfig(strategy=strategy, threshold=64)
    out = bfs(graph, src, cfg)
    np.testing.assert_array_equal(np.asarray(out.labels), np_bfs(graph, src))


@pytest.mark.parametrize("strategy", ["twc", "alb"])
def test_cc_strategies(graph, strategy):
    sg = symmetrize(graph)
    cfg = BalancerConfig(strategy=strategy, threshold=64)
    out = cc(sg, cfg)
    np.testing.assert_array_equal(np.asarray(out.labels), np_cc(sg))


@pytest.mark.parametrize("strategy", ["twc", "alb"])
def test_kcore_strategies(graph, strategy):
    sg = symmetrize(graph)
    cfg = BalancerConfig(strategy=strategy, threshold=64)
    out = kcore(sg, 4, cfg)
    np.testing.assert_array_equal(np.asarray(out.labels), np_kcore(sg, 4))


@pytest.mark.parametrize("strategy", ["twc", "alb"])
def test_pagerank_strategies(graph, strategy):
    cfg = BalancerConfig(strategy=strategy, threshold=64)
    out = pagerank(graph, cfg=cfg, max_rounds=30, tol=0.0)
    np.testing.assert_allclose(np.asarray(out.labels),
                               np_pagerank(graph, iters=30), rtol=2e-4)


def test_pagerank_conserves_mass_with_sinks():
    """Regression (dangling vertices): ranks must sum to 1 on a graph
    with sinks.  Before the fix, ``inv_out=0`` rows contributed
    nothing, mass leaked every round and ``tol`` was checked against
    deflated values."""
    # vertices 2 and 3 are sinks (no out-edges)
    g = G.from_edge_list(np.array([0, 0, 1]), np.array([1, 2, 2]), 4)
    out = pagerank(g, max_rounds=60, tol=0.0)
    rank = np.asarray(out.labels)
    assert abs(float(rank.sum()) - 1.0) < 1e-4
    np.testing.assert_allclose(rank, np_pagerank(g, iters=60), rtol=2e-4)


def test_pagerank_unchanged_without_sinks():
    """On a sink-free graph the dangling term is exactly zero, so the
    fix must not perturb results (and mass is conserved as before)."""
    n = 16
    src = np.arange(n)
    g = G.from_edge_list(src, (src + 1) % n, n)     # directed ring
    out = pagerank(g, max_rounds=30, tol=0.0)
    rank = np.asarray(out.labels)
    assert abs(float(rank.sum()) - 1.0) < 1e-4
    np.testing.assert_allclose(rank, np_pagerank(g, iters=30), rtol=2e-4)


def test_driver_loops_make_no_extra_frontier_sync(monkeypatch):
    """Regression (perf): the driver loop must converge from the
    round's own fused host counts (``return_active``) — a separate
    blocking ``jnp.any(frontier)`` per round is one extra device
    round-trip for every host-mode app."""
    from repro.core.apps import drivers as drv
    real_jnp = drv.jnp
    calls = []

    class _SpyJnp:
        def __getattr__(self, name):
            if name == "any":
                calls.append(name)
            return getattr(real_jnp, name)

    monkeypatch.setattr(drv, "jnp", _SpyJnp())
    g = G.road_grid(8, seed=0)
    out = bfs(g, 0)
    assert calls == [], "driver loop still issues jnp.any per round"
    np.testing.assert_array_equal(np.asarray(out.labels), np_bfs(g, 0))
    sg = symmetrize(g)
    calls.clear()
    kc = kcore(sg, 2)
    assert calls == []
    np.testing.assert_array_equal(np.asarray(kc.labels), np_kcore(sg, 2))


def test_cyclic_blocked_same_fixpoint(graph):
    src = G.highest_out_degree_vertex(graph)
    a = sssp(graph, src, BalancerConfig(strategy="alb", threshold=64,
                                        distribution="cyclic"))
    b = sssp(graph, src, BalancerConfig(strategy="alb", threshold=64,
                                        distribution="blocked"))
    np.testing.assert_array_equal(np.asarray(a.labels), np.asarray(b.labels))


def test_pallas_path_matches_pure(graph):
    src = G.highest_out_degree_vertex(graph)
    a = sssp(graph, src, BalancerConfig(strategy="alb", threshold=64))
    b = sssp(graph, src, BalancerConfig(strategy="alb", threshold=64,
                                        use_pallas=True))
    np.testing.assert_array_equal(np.asarray(a.labels), np.asarray(b.labels))


def test_relax_spmd_matches_host_round(graph):
    """The fully-jit SPMD round equals the host-driven round."""
    src = G.highest_out_degree_vertex(graph)
    v = graph.num_vertices
    dist = jnp.full((v,), G.INF, jnp.int32).at[src].set(0)
    frontier = single_source(v, src)
    cfg = BalancerConfig(strategy="alb", threshold=64)
    host, _ = relax(graph, dist, dist, frontier, cfg, ops.SSSP_RELAX)
    spmd = relax_spmd(graph, dist, dist, frontier, cfg, ops.SSSP_RELAX)
    np.testing.assert_array_equal(np.asarray(host), np.asarray(spmd))


def test_alb_inspector_not_fired_on_flat_graph():
    """road-style graph: the LB executor must never be invoked (the
    paper's 'negligible overhead' claim, Table 2 road-USA rows)."""
    g = G.road_grid(20, seed=0)
    src = 0
    cfg = BalancerConfig(strategy="alb", threshold=64)
    out = sssp(g, src, cfg, collect_stats=True)
    assert all(not st.lb_invoked for st in out.stats)
    assert all(st.edges_lb == 0 for st in out.stats)


def test_alb_inspector_fires_on_power_law():
    g = G.rmat(9, 8, seed=3)
    src = G.highest_out_degree_vertex(g)
    cfg = BalancerConfig(strategy="alb", threshold=64)
    out = sssp(g, src, cfg, collect_stats=True)
    assert any(st.lb_invoked for st in out.stats)


