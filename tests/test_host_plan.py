"""The two plans of a strategy (DESIGN.md section 3): the host round's
``alb`` degree ladder (``host_plan``) and the paper's bins, which the
static-capacity rounds run (``make_plan``).  The plans themselves, then
rounds of the host round under the ladder bitwise equal to rounds of
the spmd round under the three bins, with the slot counters carrying
every frontier edge exactly once."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as G
from repro.core import operators as ops
from repro.core.apps.drivers import relax_round
from repro.core.balancer import (BalancerConfig, BinSpec, counter_snapshot,
                                 effective_plan, host_plan, make_plan)
from repro.core.graph import INF

# (small_width, threshold): the defaults, thresholds that are not a
# power of two, one below the second rung, and one below small_width
LADDERS = [(8, 1024), (8, 1000), (8, 64), (8, 20), (2, 20), (4, 100),
           (16, 1024), (8, 10), (8, 9), (8, 5)]


@pytest.mark.parametrize("small_width,threshold", LADDERS)
def test_ladder_covers_every_degree_below_the_threshold_once(
        small_width, threshold):
    cfg = BalancerConfig(strategy="alb", small_width=small_width,
                         threshold=threshold)
    plan = host_plan(cfg)
    assert plan.lb == "huge"
    deg = jnp.arange(2 * threshold + 2, dtype=jnp.int32)
    valid = jnp.ones(deg.shape, bool)
    hits = np.stack([np.asarray(b.mask(deg, valid)) for b in plan.bins])
    huge = np.asarray(plan.lb_mask(deg, valid, threshold))
    per_degree = hits.sum(axis=0)
    assert per_degree[0] == 0
    np.testing.assert_array_equal(per_degree[1:threshold], 1)
    assert not (hits.any(axis=0) & huge).any()      # disjoint with huge
    assert huge[threshold:].all()
    # one pass each; the widths double from small_width, every rung
    # above the first holds (w/2, w], the last ends at threshold - 1
    widths = [b.width for b in plan.bins]
    assert widths == [small_width * 2 ** k for k in range(len(widths))]
    for b in plan.bins:
        assert b.cap == b.width and b.static_passes() == 1
        assert b.hi <= b.width
    for b in plan.bins[1:]:
        assert b.lo == b.width // 2
    assert plan.bins[-1].hi == min(threshold - 1,
                                   plan.bins[-1].width)


def test_default_ladder():
    plan = host_plan(BalancerConfig())
    assert [(b.lo, b.hi, b.width) for b in plan.bins] == [
        (0, 8, 8), (8, 16, 16), (16, 32, 32), (32, 64, 64),
        (64, 128, 128), (128, 256, 256), (256, 512, 512),
        (512, 1023, 1024)]


# make_plan's bins, which the spmd, fused, Gluon and serving-fused
# rounds run, exactly as the paper's three-bin planner gave them
_PAPER = {
    ("vertex", 1024): ((BinSpec("vertex", 1024, 0),), "none"),
    ("twc", 1024): ((BinSpec("small", 8, 0, 8, 8),
                     BinSpec("medium", 128, 8, 128, 128),
                     BinSpec("large", 1024, 128)), "none"),
    ("edge_lb", 1024): ((), "all"),
    ("alb", 1024): ((BinSpec("small", 8, 0, 8, 8),
                     BinSpec("medium", 128, 8, 128, 128),
                     BinSpec("large", 1024, 128, 1023, 1024)), "huge"),
    ("alb", 64): ((BinSpec("small", 8, 0, 8, 8),
                   BinSpec("medium", 128, 8, 63, 128),
                   BinSpec("large", 1024, 128, 63, 64)), "huge"),
}


@pytest.mark.parametrize("strategy,threshold", sorted(_PAPER))
@pytest.mark.parametrize("direction", ["push", "adaptive"])
def test_static_capacity_plan_keeps_the_paper_bins(strategy, threshold,
                                                   direction):
    cfg = BalancerConfig(strategy=strategy, threshold=threshold,
                         direction=direction)
    plan = make_plan(cfg)
    assert (plan.bins, plan.lb, plan.direction) == (
        _PAPER[strategy, threshold] + (direction,))


@pytest.mark.parametrize("cfg", [
    BalancerConfig(strategy="vertex"), BalancerConfig(strategy="twc"),
    BalancerConfig(strategy="edge_lb"),
    BalancerConfig(strategy="alb", backend="merge_path")],
    ids=["vertex", "twc", "edge_lb", "alb-merge_path"])
def test_host_plan_is_the_effective_plan_outside_alb(cfg):
    assert host_plan(cfg) == effective_plan(cfg)


def test_host_plan_differs_only_below_the_threshold():
    cfg = BalancerConfig(strategy="alb", threshold=100)
    host, static = host_plan(cfg), make_plan(cfg)
    assert (host.lb, host.direction) == (static.lb, static.direction)
    assert len(host.bins) > len(static.bins)


# ---- the host round (ladder) against the spmd round (three bins) --------

# threshold 100, small width 4: the ladder (0,4], (4,8], ..., (32,64],
# (64,99] at width 128, and a huge bin the scale-10 graph reaches
CFG = BalancerConfig(strategy="alb", threshold=100, small_width=4)
SLOTS = ("bin_edges", "lb_edges")


@pytest.fixture(scope="module")
def graph():
    return G.rmat(10, 8, seed=11)


@pytest.fixture(scope="module")
def sym_graph(graph):
    return G.symmetrized(graph)


def _out_degrees(g):
    return np.diff(np.asarray(g.row_ptr))


def _host_and_spmd(g, values, labels, frontier, cfg, op):
    """One round in each mode from the same state; the host round's
    labels, its slot counts, and whether the two agree bitwise."""
    c0 = counter_snapshot()
    host, _ = relax_round(g, values, labels, frontier, cfg, op,
                          mode="host")
    c1 = counter_snapshot()
    spmd, _ = relax_round(g, values, labels, frontier, cfg, op,
                          mode="spmd")
    np.testing.assert_array_equal(np.asarray(host), np.asarray(spmd))
    return host, sum(c1[k] - c0[k] for k in SLOTS)


def _traverse(g, cfg, op, sources):
    """A min-combine traversal from ``sources`` (one row per source),
    host and spmd rounds compared at every round."""
    v = g.num_vertices
    b = len(sources)
    rows = jnp.arange(b)
    labels = jnp.full((b, v), INF, jnp.int32).at[rows, sources].set(0)
    frontier = jnp.zeros((b, v), bool).at[rows, sources].set(True)
    if b == 1:
        labels, frontier = labels[0], frontier[0]
    deg = _out_degrees(g)
    rounds = 0
    while bool(jnp.any(frontier)):
        new, carried = _host_and_spmd(g, labels, labels, frontier, cfg, op)
        union = np.asarray(frontier).reshape(-1, v).any(axis=0)
        if cfg.direction == "push":
            assert carried == deg[union].sum()
        else:   # a pull round enumerates every in-edge of the graph
            assert carried == g.num_edges
        frontier = new < labels
        labels = new
        rounds += 1
    assert rounds > 2


_MIN_CASES = [
    # app, direction, backend, batch
    ("bfs", "push", "xla", 1), ("sssp", "push", "xla", 1),
    ("bfs", "pull", "xla", 1), ("sssp", "pull", "xla", 1),
    ("bfs", "push", "pallas", 1), ("bfs", "pull", "pallas", 1),
    ("bfs", "push", "xla", 3), ("sssp", "pull", "xla", 3),
    ("bfs", "push", "pallas", 3),
]


@pytest.mark.parametrize("app,direction,backend,batch", _MIN_CASES)
def test_host_ladder_rounds_equal_spmd_rounds(graph, app, direction,
                                              backend, batch):
    cfg = dataclasses.replace(CFG, direction=direction, backend=backend)
    op = ops.BFS_HOP if app == "bfs" else ops.SSSP_RELAX
    top = np.argsort(-_out_degrees(graph), kind="stable")
    sources = [int(s) for s in top[[0, 7, 300][:batch]]]
    _traverse(graph, cfg, op, sources)


@pytest.mark.parametrize("backend,batch", [("xla", 1), ("pallas", 1),
                                           ("xla", 2)])
def test_host_ladder_add_combine_equals_spmd(sym_graph, backend, batch):
    """kcore's degree decrement (add-combine): each frontier edge must
    land exactly once, or a degree drifts."""
    cfg = dataclasses.replace(CFG, backend=backend)
    g = sym_graph
    v = g.num_vertices
    deg = _out_degrees(g)
    rng = np.random.default_rng(3)
    labels = jnp.broadcast_to(jnp.asarray(deg, jnp.int32), (batch, v))
    for density in (0.02, 0.3, 1.0):
        fr = rng.random((batch, v)) < density
        frontier = jnp.asarray(fr if batch > 1 else fr[0])
        lab = labels if batch > 1 else labels[0]
        new, carried = _host_and_spmd(g, lab, lab, frontier, cfg,
                                      ops.KCORE_DEC)
        assert carried == deg[fr.any(axis=0)].sum()
        # every in-neighbour in the frontier took one off each degree
        want = np.stack([deg - np.bincount(
            np.asarray(g.col_idx)[np.repeat(fr[r], deg)], minlength=v)
            for r in range(batch)])
        np.testing.assert_array_equal(
            np.asarray(new).reshape(batch, v), want)
