"""Multi-device graph analytics: CuSP-analog partitioning + Gluon-analog
BSP sync, the paper's D-IrGL(ALB) system (Sections 5/6.2).

Re-execs itself on CPU with 4 forced host devices (CPU stand-ins for
GPUs); on a TPU host, ``python chip_smoke.py --chips 4`` runs the same
partitioned path on four chips.

  PYTHONPATH=src python examples/distributed_graph.py
"""
import os
import subprocess
import sys

if os.environ.get("_REPRO_INNER") != "1":
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["_REPRO_INNER"] = "1"
    sys.exit(subprocess.run([sys.executable, __file__], env=env).returncode)

import numpy as np
import jax

from repro.compile_cache import enable_compile_cache
from repro.core import graph as G
from repro.core.partition import partition, partition_stats
from repro.core import gluon
from repro.core.balancer import BalancerConfig
from repro.core.apps import sssp

enable_compile_cache()

g = G.rmat(12, 16, seed=0)
src = G.highest_out_degree_vertex(g)
print(f"devices: {len(jax.devices())} {jax.devices()[0].platform}; "
      f"graph |V|={g.num_vertices} "
      f"|E|={g.num_edges}")

ref = sssp(g, src, BalancerConfig(strategy="alb", threshold=1024))

mesh = gluon.device_mesh(4)
for policy in ["oec", "iec", "cvc"]:
    sg, meta = partition(g, 4, policy)
    st = partition_stats(sg, meta)
    for strat in ["twc", "alb"]:
        cfg = BalancerConfig(strategy=strat, threshold=1024)
        for sync in ["replicated", "mirror"]:
            labels, rounds, secs, stats = gluon.sssp_distributed(
                sg, mesh, src, cfg, collect_stats=True,
                sync=sync, meta=meta)
            ok = np.array_equal(np.asarray(labels), np.asarray(ref.labels))
            comm = sum(st.bytes_synced
                       for per_round in stats for st in per_round)
            print(f"{policy}/{strat:4s}/{sync:10s}: {secs * 1e3:7.1f} ms  "
                  f"rounds={rounds} edge-imbalance={st['imbalance']:.2f} "
                  f"replication={st['replication_factor']:.2f} "
                  f"synced={comm / 1024:.1f}KiB correct={ok}")
