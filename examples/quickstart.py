"""Quickstart: the paper's scenario end-to-end in 40 lines.

Builds a power-law graph, runs SSSP under every load-balancing
strategy, and shows the ALB inspector firing only where imbalance
exists.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import graph as G
from repro.core.balancer import BalancerConfig
from repro.core.apps import sssp

enable_compile_cache()

# power-law graph (rmat): a few vertices own most edges
g = G.rmat(scale=12, edge_factor=16, seed=0)
src = G.highest_out_degree_vertex(g)
print(f"graph: |V|={g.num_vertices} |E|={g.num_edges} "
      f"max_out_degree={g.max_out_degree()}")

results = {}
for strategy in ["vertex", "twc", "edge_lb", "alb"]:
    cfg = BalancerConfig(strategy=strategy, threshold=256)
    r = sssp(g, src, cfg, collect_stats=True)
    results[strategy] = r
    fired = sum(st.lb_invoked for st in r.stats)
    print(f"{strategy:8s}: {r.seconds * 1e3:8.1f} ms  "
          f"rounds={r.rounds}  LB-kernel-fired={fired}/{len(r.stats)}")

# all strategies agree on the fixpoint
base = np.asarray(results["twc"].labels)
for s, r in results.items():
    assert np.array_equal(np.asarray(r.labels), base), s
print("all strategies computed identical shortest paths ✓")

# flat graph: the inspector never fires (paper: 'negligible overhead')
road = G.road_grid(48, seed=0)
r = sssp(road, 0, BalancerConfig(strategy="alb", threshold=256),
         collect_stats=True)
print(f"road graph: LB fired "
      f"{sum(st.lb_invoked for st in r.stats)}/{len(r.stats)} rounds "
      f"(adaptive: stays out of the way)")
