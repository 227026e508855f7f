"""Unified executor registry: the fully-jit SPMD round must match the
host-driven round for every strategy and both executor backends, and
the Pallas kernels must work inside ``shard_map`` (the Gluon runtime).

This is the acceptance suite for the executor-registry refactor
(DESIGN.md section 3): one planner, two execution modes, two backends.
"""
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import graph as G
from repro.core.balancer import (BalancerConfig, RoundStats, relax,
                                 relax_spmd, make_plan, host_plan)
from repro.core.frontier import single_source
from repro.core import operators as ops
from repro.core import gluon
from repro.core.partition import partition
from repro.core.apps import bfs, sssp, cc, pagerank

STRATS = ["vertex", "twc", "edge_lb", "alb"]


@pytest.fixture(scope="module", params=["rmat", "road"])
def graph(request):
    if request.param == "rmat":
        return G.rmat(9, 8, seed=3)
    return G.road_grid(16, seed=3)


def _sssp_round_inputs(g):
    src = G.highest_out_degree_vertex(g)
    v = g.num_vertices
    dist = jnp.full((v,), G.INF, jnp.int32).at[src].set(0)
    return dist, single_source(v, src)


# ---------------- single-round parity, all strategies x backends ----------

@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("strategy", STRATS)
def test_relax_spmd_matches_host_all_strategies(graph, strategy, use_pallas):
    dist, frontier = _sssp_round_inputs(graph)
    cfg = BalancerConfig(strategy=strategy, threshold=64,
                         use_pallas=use_pallas)
    host, _ = relax(graph, dist, dist, frontier, cfg, ops.SSSP_RELAX)
    spmd = relax_spmd(graph, dist, dist, frontier, cfg, ops.SSSP_RELAX)
    np.testing.assert_array_equal(np.asarray(host), np.asarray(spmd))


def test_spmd_pallas_matches_spmd_xla_round(graph):
    dist, frontier = _sssp_round_inputs(graph)
    outs = []
    for up in [False, True]:
        cfg = BalancerConfig(strategy="alb", threshold=64, use_pallas=up)
        outs.append(relax_spmd(graph, dist, dist, frontier, cfg,
                               ops.SSSP_RELAX))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))


# ---------------- full apps in spmd mode, pallas vs xla -------------------

@pytest.mark.parametrize("use_pallas", [False, True])
def test_apps_spmd_mode_match_host_mode(graph, use_pallas):
    """bfs/sssp/cc/pagerank driven by relax_spmd == host round labels."""
    src = G.highest_out_degree_vertex(graph)
    cfg = BalancerConfig(strategy="alb", threshold=64,
                         use_pallas=use_pallas)
    ref_cfg = BalancerConfig(strategy="alb", threshold=64)
    np.testing.assert_array_equal(
        np.asarray(sssp(graph, src, cfg, mode="spmd").labels),
        np.asarray(sssp(graph, src, ref_cfg).labels))
    np.testing.assert_array_equal(
        np.asarray(bfs(graph, src, cfg, mode="spmd").labels),
        np.asarray(bfs(graph, src, ref_cfg).labels))
    np.testing.assert_array_equal(
        np.asarray(cc(graph, cfg, mode="spmd").labels),
        np.asarray(cc(graph, ref_cfg).labels))
    # float scatter-add order differs between enumerations: allclose
    np.testing.assert_allclose(
        np.asarray(pagerank(graph, cfg=cfg, max_rounds=15, tol=0.0,
                            mode="spmd").labels),
        np.asarray(pagerank(graph, cfg=ref_cfg, max_rounds=15,
                            tol=0.0).labels), rtol=1e-5, atol=1e-8)


# ---------------- jit-safe instrumentation --------------------------------

def test_spmd_stats_match_host_stats():
    g = G.rmat(9, 8, seed=3)
    dist, frontier = _sssp_round_inputs(g)
    cfg = BalancerConfig(strategy="alb", threshold=64)
    _, hst = relax(g, dist, dist, frontier, cfg, ops.SSSP_RELAX,
                   collect_stats=True)
    _, dst = relax_spmd(g, dist, dist, frontier, cfg, ops.SSSP_RELAX,
                        collect_stats=True)
    sst = RoundStats.from_device(dst)
    assert sst.frontier_size == hst.frontier_size
    assert sst.edges_twc == hst.edges_twc
    assert sst.edges_lb == hst.edges_lb
    assert sst.lb_invoked == hst.lb_invoked


def test_spmd_stats_inspector_adaptive_on_flat_graph():
    """road-style graph: the SPMD inspector must never fire the LB
    executor (Table 2 'negligible overhead' claim, now jit-safe)."""
    g = G.road_grid(20, seed=0)
    cfg = BalancerConfig(strategy="alb", threshold=64)
    out = sssp(g, 0, cfg, collect_stats=True, mode="spmd")
    assert out.stats
    assert all(not st.lb_invoked for st in out.stats)
    assert all(st.edges_lb == 0 for st in out.stats)


def test_spmd_stats_lb_fires_and_balances_on_power_law():
    g = G.rmat(9, 8, seed=3)
    src = G.highest_out_degree_vertex(g)
    cfg = BalancerConfig(strategy="alb", threshold=64)
    out = sssp(g, src, cfg, collect_stats=True, mode="spmd")
    fired = [st for st in out.stats if st.lb_invoked]
    assert fired
    for st in fired:
        assert st.edges_lb > 0


# ---------------- pallas inside shard_map (the tentpole claim) ------------

@pytest.mark.parametrize("use_pallas", [False, True])
def test_gluon_runtime_runs_both_backends(graph, use_pallas):
    """The distributed round (shard_map over a 1-device mesh exercises
    the full machinery) must produce the reference labels with the
    Pallas kernels dispatched inside shard_map."""
    src = G.highest_out_degree_vertex(graph)
    mesh = gluon.device_mesh(1)
    sg, _ = partition(graph, 1, "oec")
    cfg = BalancerConfig(strategy="alb", threshold=64,
                         use_pallas=use_pallas)
    ref = sssp(graph, src, BalancerConfig(strategy="alb", threshold=64))
    labels, rounds, _ = gluon.sssp_distributed(sg, mesh, src, cfg)
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(ref.labels))

    bref = bfs(graph, src, BalancerConfig(strategy="alb", threshold=64))
    blabels, _, _ = gluon.bfs_distributed(sg, mesh, src, cfg)
    np.testing.assert_array_equal(np.asarray(blabels),
                                  np.asarray(bref.labels))

    rg = G.reverse_graph(graph)
    srg, _ = partition(rg, 1, "oec")
    pref = pagerank(graph, max_rounds=10, tol=0.0)
    rank, _, _ = gluon.pagerank_distributed(srg, mesh, graph.out_degrees(),
                                            cfg=cfg, max_rounds=10, tol=0.0)
    np.testing.assert_allclose(np.asarray(rank), np.asarray(pref.labels),
                               atol=1e-6)


def test_gluon_collect_stats_through_shard_map():
    g = G.rmat(9, 8, seed=3)
    src = G.highest_out_degree_vertex(g)
    mesh = gluon.device_mesh(1)
    sg, _ = partition(g, 1, "oec")
    cfg = BalancerConfig(strategy="alb", threshold=64)
    labels, rounds, _, stats = gluon.sssp_distributed(
        sg, mesh, src, cfg, collect_stats=True)
    assert len(stats) == rounds
    assert all(len(per_round) == 1 for per_round in stats)     # 1 device
    assert any(st.lb_invoked for per_round in stats for st in per_round)
    # replicated sync reports the all-reduce baseline volume per round
    v = g.num_vertices
    assert all(st.bytes_synced == v * 4
               for per_round in stats for st in per_round)
    ref = sssp(g, src, cfg)
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(ref.labels))


def test_gluon_mirror_sync_single_device_parity():
    """sync='mirror' on a 1-device mesh: the ring is empty, but the
    owned-state loop, dirty mask, and master assembly all run."""
    g = G.rmat(9, 8, seed=3)
    src = G.highest_out_degree_vertex(g)
    mesh = gluon.device_mesh(1)
    sg, meta = partition(g, 1, "oec")
    cfg = BalancerConfig(strategy="alb", threshold=64)
    ref = sssp(g, src, cfg)
    labels, rounds, _, stats = gluon.sssp_distributed(
        sg, mesh, src, cfg, collect_stats=True, sync="mirror", meta=meta)
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(ref.labels))
    # no peers -> no mirror traffic at all
    assert all(st.bytes_synced == 0
               for per_round in stats for st in per_round)


def test_gluon_kcore_distributed_single_device():
    from repro.core.apps import kcore
    g = G.symmetrized(G.rmat(9, 8, seed=3))
    mesh = gluon.device_mesh(1)
    sg, meta = partition(g, 1, "oec")
    cfg = BalancerConfig(strategy="alb", threshold=64)
    ref = kcore(g, 8, cfg)
    for sync in ["replicated", "mirror"]:
        labels, rounds, _ = gluon.kcore_distributed(
            sg, mesh, 8, cfg, sync=sync, meta=meta)
        np.testing.assert_array_equal(np.asarray(labels),
                                      np.asarray(ref.labels))


# ---------------- multi-device (subprocess, slow) -------------------------

MULTIDEV_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import graph as G
from repro.core.partition import partition
from repro.core import gluon
from repro.core.balancer import BalancerConfig
from repro.core.apps import sssp, cc, pagerank

assert len(jax.devices()) == 4, jax.devices()
g = G.rmat(9, 8, seed=5)
src = G.highest_out_degree_vertex(g)
mesh = gluon.device_mesh(4)
sg, meta = partition(g, 4, "oec")
cfg = BalancerConfig(strategy="alb", threshold=64, use_pallas=True)
ref = sssp(g, src, BalancerConfig(strategy="alb", threshold=64))
labels, rounds, secs, stats = gluon.sssp_distributed(
    sg, mesh, src, cfg, collect_stats=True)
assert np.array_equal(np.asarray(labels), np.asarray(ref.labels))
assert all(len(per_round) == 4 for per_round in stats)
# per-device adaptivity: at least one round where some device fired the
# LB executor and some device skipped it would show as mixed flags; at
# minimum the flags must be well-formed booleans and edge counts consistent
for per_round in stats:
    for st in per_round:
        assert (st.edges_lb > 0) == bool(st.lb_invoked)
# pallas kernels inside shard_map under the mirror substrate too
mlabels, _, _ = gluon.sssp_distributed(sg, mesh, src, cfg,
                                       sync="mirror", meta=meta)
assert np.array_equal(np.asarray(mlabels), np.asarray(ref.labels))
rg = G.reverse_graph(g)
srg, rmeta = partition(rg, 4, "oec")
rank, _, _ = gluon.pagerank_distributed(
    srg, mesh, g.out_degrees(), cfg=cfg, max_rounds=10, tol=0.0)
pref = pagerank(g, max_rounds=10, tol=0.0)
assert np.allclose(np.asarray(rank), np.asarray(pref.labels), atol=1e-6)
print("SPMD_PALLAS_OK")
"""


@pytest.mark.slow
def test_multidevice_pallas_matches_single_device():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SPMD_PALLAS_OK" in out.stdout


# ---------------- fused host-round decision transfer ----------------------

def test_host_round_counts_layout():
    """relax's per-round host decisions come from ONE fused int32 vector
    (one device->host transfer) whose entries match the individual
    reductions it replaced."""
    from repro.core.balancer import _host_round_counts
    g = G.rmat(9, 8, seed=3)
    dist, frontier = _sssp_round_inputs(g)
    cfg = BalancerConfig(strategy="alb", threshold=64)
    cnt, union = _host_round_counts(g, frontier, cfg)
    cnt = np.asarray(cnt)
    np.testing.assert_array_equal(np.asarray(union), np.asarray(frontier))
    # one count, a triplet per bin of the host plan, the inspector pair:
    # threshold 64 gives the ladder (0,8], (8,16], (16,32], (32,63],
    # so 1 + 3 * 4 + 2 = 15 entries
    plan = host_plan(cfg)
    assert len(plan.bins) == 4
    assert cnt.shape == (1 + 3 * len(plan.bins) + 2,)
    deg = np.asarray(g.row_ptr[1:]) - np.asarray(g.row_ptr[:-1])
    f = np.asarray(frontier)
    assert cnt[0] == f.sum()
    k = 1
    for spec in plan.bins:
        m = np.asarray(spec.mask(jnp.asarray(deg), jnp.asarray(f)))
        assert cnt[k] == m.sum()
        assert cnt[k + 1] == (deg * m).max(initial=0)
        assert cnt[k + 2] == (deg * m).sum()
        k += 3
    hm = f & (deg >= cfg.threshold)
    assert cnt[k] == hm.sum() and cnt[k + 1] == (deg * hm).sum()


# ---------------- planner unit coverage -----------------------------------

def test_plan_shapes():
    alb = make_plan(BalancerConfig(strategy="alb", threshold=64))
    assert alb.lb == "huge" and len(alb.bins) == 3
    assert all(b.static_passes() is not None for b in alb.bins)
    twc = make_plan(BalancerConfig(strategy="twc"))
    assert twc.lb == "none" and twc.bins[-1].static_passes() is None
    assert make_plan(BalancerConfig(strategy="edge_lb")).lb == "all"
    vx = make_plan(BalancerConfig(strategy="vertex"))
    assert vx.lb == "none" and len(vx.bins) == 1
