"""Fig 6/10 analogue: multi-device scaling of D-IrGL(TWC) vs
D-IrGL(ALB) — BSP rounds over partitioned graphs, 1..8 devices, under
both sync substrates (``replicated`` all-reduce vs ``mirror``
boundary exchange, DESIGN.md section 6).

Besides the CSV rows, writes ``benchmarks/out/fig6_scaling.json`` with
per-round communication volume so the perf trajectory tracks what
actually crosses the interconnect, not just wall clock.  Every row
carries the wire codec name (``wire``, DESIGN.md section 14) plus the
per-round logical volume (``bytes_synced_per_round``, index side
included), the post-encode volume (``bytes_wire_per_round``), and the
per-round compression ratio ``bytes_wire / bytes_synced``; rows also
carry ``mode`` (host vs fused round loop, DESIGN.md section 11) and
``host_transfers`` — the number of blocking device->host sync points
the traversal performed.

Timed rows run the default ``identity`` codec; the codec-comparison
rows (``delta`` / ``bitmap``) are instrumented-only (host mode), since
the compression trajectory is structural, not a wall-clock claim.
``quantize`` is absent by construction: sssp's min-combine declares no
safe narrowing, so the config-time raise is asserted instead (the
``--smoke`` CI run keeps that gate exercised).

Re-execs itself on CPU (``JAX_PLATFORMS=cpu``) with a forced host
device count: the virtual devices exist only on the CPU backend, and
the child must not take a chip the parent may hold.  Every row and the
JSON name the platform the child measured (``cpu``).
``--smoke``: a small-graph, two-mesh subset for the benchmark-smoke CI
job.
"""
from __future__ import annotations

import os
import subprocess
import sys

MAX_DEV = 8
OUT_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "out", "fig6_scaling.json")

WIRE_CODECS = ["identity", "delta", "bitmap"]


def run(smoke: bool = False):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count="
                          f"{MAX_DEV}").strip()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    argv = [sys.executable, "-m", "benchmarks.fig6_scaling", "--inner"]
    if smoke:
        argv.append("--smoke")
    r = subprocess.run(argv, env=env, cwd=root,
                       capture_output=True, text=True, timeout=3600)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        sys.stderr.write(r.stderr[-3000:])
        raise RuntimeError("fig6 inner run failed")


def _comm_rows(gluon, sg, mesh, src, cfg_base, sync, meta, max_rounds):
    """One instrumented (host-mode) run per wire codec: the comm-volume
    trajectory the ROADMAP asks for, as (wire -> per-round byte lists
    and ratios)."""
    import dataclasses
    out = {}
    for wname in WIRE_CODECS:
        cfg = dataclasses.replace(cfg_base, wire=wname)
        _, _, _, stats = gluon.sssp_distributed(
            sg, mesh, src, cfg, max_rounds=max_rounds,
            collect_stats=True, sync=sync, meta=meta)
        logical = [int(sum(st.bytes_synced for st in pr))
                   for pr in stats]
        wired = [int(sum(st.bytes_wire for st in pr)) for pr in stats]
        out[wname] = dict(
            bytes_synced_per_round=logical,
            bytes_wire_per_round=wired,
            compression_ratio_per_round=[
                (w / b) if b else 1.0 for b, w in zip(logical, wired)],
            bytes_synced_total=sum(logical),
            bytes_wire_total=sum(wired))
    return out


def inner(smoke: bool = False):
    import json
    import time
    import jax
    from repro.core import graph as G
    from repro.core.partition import partition
    from repro.core import gluon
    from repro.core.balancer import BalancerConfig, host_transfer_count
    from .common import emit

    scale, ef = (10, 8) if smoke else (13, 16)
    g = G.rmat(scale, ef, seed=1)
    src = G.highest_out_degree_vertex(g)
    dev_counts = [2, 4] if smoke else [1, 2, 4, 8]
    strategies = ["alb"] if smoke else ["twc", "alb"]
    max_rounds = 200
    platform = jax.devices()[0].platform

    # config-time gate: quantize on sssp (no declared narrowing) must
    # refuse to run — keep that contract exercised wherever fig6 runs
    mesh0 = gluon.device_mesh(dev_counts[0])
    sg0, meta0 = partition(g, dev_counts[0], "oec")
    try:
        gluon.sssp_distributed(sg0, mesh0, src,
                               BalancerConfig(wire="quantize"),
                               sync="mirror", meta=meta0)
    except ValueError:
        pass
    else:
        raise AssertionError(
            "wire='quantize' must raise at config time for sssp")

    rows = []
    for ndev in dev_counts:
        mesh = gluon.device_mesh(ndev)
        sg, meta = partition(g, ndev, "oec")
        for strat in strategies:
            cfg = BalancerConfig(strategy=strat, threshold=1024)
            for sync in ["replicated", "mirror"]:
                # instrumented runs: comm volume per round, one per
                # codec (host mode only — fused+collect_stats is
                # rejected)
                comm = _comm_rows(gluon, sg, mesh, src, cfg, sync,
                                  meta, max_rounds)
                for mode in ["host", "fused"]:
                    # warmup (compile)
                    gluon.sssp_distributed(sg, mesh, src, cfg,
                                           max_rounds=max_rounds,
                                           sync=sync, meta=meta,
                                           mode=mode)
                    t_sync = host_transfer_count()
                    t0 = time.perf_counter()
                    labels, rounds, _ = gluon.sssp_distributed(
                        sg, mesh, src, cfg, max_rounds=max_rounds,
                        sync=sync, meta=meta, mode=mode)
                    secs = time.perf_counter() - t0
                    ht = host_transfer_count() - t_sync
                    c = comm["identity"]
                    emit(f"fig6/sssp/{strat}/gpus{ndev}/{sync}/{mode}",
                         secs,
                         f"rounds={rounds};"
                         f"bytes_total={c['bytes_synced_total']};"
                         f"ht={ht};platform={platform}")
                    rows.append(dict(
                        app="sssp", strategy=strat, num_devices=ndev,
                        sync=sync, mode=mode, wire="identity",
                        seconds=secs, rounds=rounds, host_transfers=ht,
                        replication_factor=meta.replication_factor,
                        **c))
                # codec-comparison rows: structural, untimed
                for wname in WIRE_CODECS[1:]:
                    rows.append(dict(
                        app="sssp", strategy=strat, num_devices=ndev,
                        sync=sync, mode="host", wire=wname,
                        seconds=None, rounds=len(
                            comm[wname]["bytes_synced_per_round"]),
                        host_transfers=None,
                        replication_factor=meta.replication_factor,
                        **comm[wname]))
    os.makedirs(os.path.dirname(OUT_JSON), exist_ok=True)
    with open(OUT_JSON, "w") as f:
        json.dump(dict(
            figure="fig6_scaling",
            smoke=smoke,
            platform=platform,
            graph=dict(kind="rmat", scale=scale, edge_factor=ef,
                       num_vertices=g.num_vertices,
                       num_edges=g.num_edges),
            wire_codecs=WIRE_CODECS,
            replicated_baseline_bytes_per_round={
                str(d): g.num_vertices * 4 * d for d in dev_counts},
            rows=rows), f, indent=2)
    print(f"# wrote {OUT_JSON}", flush=True)


if __name__ == "__main__":
    if "--inner" in sys.argv:
        inner(smoke="--smoke" in sys.argv)
    else:
        run(smoke="--smoke" in sys.argv)
