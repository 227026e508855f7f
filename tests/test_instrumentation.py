"""The program's own instrumentation of the host round: the slot
counters against hand-computed values, the named scopes of the XLA
edge passes, and the round's profiler spans (DESIGN.md section 11)."""
import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as G
from repro.core import operators as ops
from repro.core.apps import bfs
from repro.core.balancer import (BalancerConfig, _bin_pass, _lb_pass,
                                 counter_snapshot, host_transfer_count,
                                 relax)
from repro.core.graph import INF

SLOT_COUNTERS = ("bin_slots", "bin_edges", "lb_slots", "lb_edges")

# the host round's ladder: 0 < d <= 2 (width 2), 2 < d <= 4 (width 4),
# 4 < d <= 8 (width 8), 8 < d <= 16 (width 16), 16 < d <= 19 (width
# 32), one pass each; huge d >= 20 (the LB pass); 48 tiles, so the LB
# pass rounds its 32-id bucket up to 48 ids
CFG = BalancerConfig(strategy="alb", threshold=20, small_width=2,
                     medium_width=4, large_width=8, num_tiles=48,
                     lb_tile_edges=16)
FRONTIER = (0, 1, 2, 3, 4)


@pytest.fixture(scope="module")
def hand_graph():
    """64 vertices.  Out-degrees of the frontier 0..4: 1, 2, 3, 11, 25
    (one vertex in each bin and one above the threshold).  In-degrees:
    10 has 3, 11 has 2, every other target of 0..4 has 1; 30..39 point
    at 6 (in-degree 10) and 40..63 at 5 (in-degree 24)."""
    out = {0: [10], 1: [10, 11], 2: [10, 11, 12], 3: list(range(13, 24)),
           4: list(range(24, 49))}
    out.update({u: [6] for u in range(30, 40)})
    out.update({u: [5] for u in range(40, 64)})
    src = np.array([u for u in sorted(out) for _ in out[u]])
    dst = np.array([w for u in sorted(out) for w in out[u]])
    return G.from_edge_list(src, dst, 64)


def _one_round(g, direction):
    v = g.num_vertices
    labels = jnp.full((v,), INF, jnp.int32).at[jnp.array(FRONTIER)].set(0)
    frontier = jnp.zeros((v,), bool).at[jnp.array(FRONTIER)].set(True)
    cfg = dataclasses.replace(CFG, direction=direction)
    relax(g, labels, labels, frontier, cfg, ops.BFS_HOP)   # builds caches
    c0, t0 = counter_snapshot(), host_transfer_count()
    relax(g, labels, labels, frontier, cfg, ops.BFS_HOP)
    c1 = counter_snapshot()
    return ({k: c1[k] - c0[k] for k in SLOT_COUNTERS},
            host_transfer_count() - t0)


# per bin: bucket(members) x width; buckets are at least 64, empty bins
# issue nothing
#   push, by out-degree: (0,2] {0, 1} 64x2, (2,4] {2} 64x4, (8,16] {3}
#     64x16: 128 + 256 + 1024 = 1408 slots for 1 + 2 + 3 + 11 = 17
#     edges; LB {4}: 25 edges, bucket 32, rounded to 48 ids
#   pull, by in-degree over every vertex with in-edges: (0,2] (38
#     vertices, 39 edges) 64x2, (2,4] {10} 64x4, (8,16] {6} 64x16 =
#     1408 slots for 39 + 3 + 10 = 52 edges; LB {5}: 24 edges, 48 ids
@pytest.mark.parametrize("direction,want", [
    ("push", {"bin_slots": 1408, "bin_edges": 17, "lb_slots": 48,
              "lb_edges": 25}),
    ("pull", {"bin_slots": 1408, "bin_edges": 52, "lb_slots": 48,
              "lb_edges": 24}),
])
def test_slot_counters_of_one_round(hand_graph, direction, want):
    counts, transfers = _one_round(hand_graph, direction)
    assert counts == want
    # the counters add no device->host transfer to the round's one
    assert transfers == 1


def test_counter_snapshot_holds_every_counter():
    snap = counter_snapshot()
    assert set(SLOT_COUNTERS) | {"host_transfers"} <= set(snap)
    assert snap["host_transfers"] == host_transfer_count()
    snap["bin_slots"] += 1                  # a copy, not the registry
    assert counter_snapshot()["bin_slots"] == snap["bin_slots"] - 1


@pytest.mark.parametrize("threshold", [64, 1024])
def test_bfs_edge_counters_sum_to_the_reached_out_degrees(threshold):
    g = G.rmat(9, 8, seed=3)
    src = G.highest_out_degree_vertex(g)
    c0 = counter_snapshot()
    out = bfs(g, src, BalancerConfig(strategy="alb", threshold=threshold))
    c1 = counter_snapshot()
    d = {k: c1[k] - c0[k] for k in SLOT_COUNTERS}
    deg = np.diff(np.asarray(g.row_ptr))
    reached = np.asarray(out.labels) < INF
    # a BFS frontier holds each reached vertex once
    assert d["bin_edges"] + d["lb_edges"] == deg[reached].sum()
    assert (d["lb_edges"] > 0) == (deg[reached].max() >= threshold)
    assert d["bin_slots"] >= d["bin_edges"] and d["lb_slots"] >= d["lb_edges"]
    assert out.host_transfers == out.rounds + 1


def _lowered_text(fn, *args, **static):
    lowered = fn.lower(*args, **static)
    return lowered.as_text(debug_info=True)


def test_edge_passes_carry_named_scopes():
    g = G.rmat(6, 4, seed=3)
    v = g.num_vertices
    lab = jnp.zeros((1, v), jnp.int32)
    fm = jnp.zeros((1, v), bool)
    idx = jnp.zeros((8,), jnp.int32)
    bin_txt = _lowered_text(_bin_pass, g, lab, lab, fm, idx, idx, idx,
                            width=8, op=ops.BFS_HOP, chunk=0)
    lb_txt = _lowered_text(_lb_pass, g, lab, lab, fm, idx, idx, idx,
                           jnp.int32(8), ecap=64, op=ops.BFS_HOP,
                           distribution="cyclic", num_tiles=4,
                           tile_edges=0)
    for scope in ("edges", "sources", "combine"):
        assert f"jit(_bin_pass_impl)/{scope}/" in bin_txt
        assert f"jit(_lb_pass_impl)/{scope}/" in lb_txt
    assert "jit(_lb_pass_impl)/enumerate/" in lb_txt
    assert "/enumerate/" not in bin_txt
    # the scatter sits under combine, the edge gathers under edges
    assert "jit(_bin_pass_impl)/combine/scatter-min" in bin_txt
    assert "jit(_bin_pass_impl)/edges/gather" in bin_txt
    # program names, which the benchmark's layer files read, unchanged
    assert "module @jit__bin_pass_impl" in bin_txt
    assert "module @jit__lb_pass_impl" in lb_txt


def test_profiler_trace_holds_the_round_spans(tmp_path):
    from jax.profiler import ProfileData
    g = G.rmat(9, 8, seed=3)
    src = G.highest_out_degree_vertex(g)
    cfg = BalancerConfig(strategy="alb", threshold=64)
    bfs(g, src, cfg)                                    # compile first
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = bfs(g, src, cfg)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [(e.name, e.start_ns, e.end_ns)
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("graph.")]
    rounds = sorted((s, e) for n, s, e in events if n == "graph.round")

    def children(s, e):
        return sorted(n for n, cs, ce in events
                      if n != "graph.round" and s <= cs and ce <= e)

    # one span per round, and one more for the probe that finds the
    # frontier empty
    assert len(rounds) == out.rounds + 1
    for s, e in rounds[:-1]:
        assert children(s, e) == ["graph.counts", "graph.passes",
                                  "graph.plan", "graph.update"]
    assert children(*rounds[-1]) == ["graph.counts"]
