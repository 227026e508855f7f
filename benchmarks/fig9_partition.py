"""Fig 9 analogue: ALB under different partition policies (IEC / OEC /
CVC) — the paper's point: whatever the partitioner does about
inter-device balance, intra-device thread-block imbalance remains and
ALB fixes it.

Re-execs itself on CPU (``JAX_PLATFORMS=cpu``) with four forced host
devices; every row names that platform."""
from __future__ import annotations

import os
import subprocess
import sys

NDEV = 4


def run():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count="
                          f"{NDEV}").strip()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    r = subprocess.run([sys.executable, "-m", "benchmarks.fig9_partition",
                        "--inner"], env=env, cwd=root,
                       capture_output=True, text=True, timeout=3600)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        sys.stderr.write(r.stderr[-3000:])
        raise RuntimeError("fig9 inner run failed")


def inner():
    import time
    import jax
    from repro.core import graph as G
    from repro.core.partition import partition, partition_stats
    from repro.core import gluon
    from repro.core.balancer import BalancerConfig
    from .common import emit

    g = G.rmat(13, 16, seed=1)
    src = G.highest_out_degree_vertex(g)
    mesh = gluon.device_mesh(NDEV)
    for policy in ["oec", "iec", "cvc"]:
        sg, meta = partition(g, NDEV, policy)
        st = partition_stats(sg, meta)
        for strat in ["twc", "alb"]:
            cfg = BalancerConfig(strategy=strat, threshold=1024)
            for sync in ["replicated", "mirror"]:
                gluon.sssp_distributed(sg, mesh, src, cfg, max_rounds=200,
                                       sync=sync, meta=meta)
                t0 = time.perf_counter()
                gluon.sssp_distributed(sg, mesh, src, cfg, max_rounds=200,
                                       sync=sync, meta=meta)
                secs = time.perf_counter() - t0
                emit(f"fig9/sssp/{policy}/{strat}/{sync}", secs,
                     f"edge_imbalance={st['imbalance']:.2f};"
                     f"replication={st['replication_factor']:.2f};"
                     f"platform={jax.devices()[0].platform}")


if __name__ == "__main__":
    if "--inner" in sys.argv:
        inner()
    else:
        run()
