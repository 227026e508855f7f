"""Benchmark aggregator: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run             # everything
  PYTHONPATH=src python -m benchmarks.run table2 fig8 # subset

Selectors and what each script reproduces:

* ``table2``   (table2_strategies.py)   — Table 2: wall clock per
  (input x app x strategy); also times the fully-jit SPMD round
  (``alb_spmd`` rows) and derives ALB-vs-TWC speedups.
* ``fig6``     (fig6_scaling.py)        — Fig 6/10: 1..8-device BSP
  scaling of the Gluon-analog runtime, TWC vs ALB, replicated vs
  mirror sync; also writes benchmarks/out/fig6_scaling.json with
  per-round comm volume (bytes_synced).
* ``fig8``     (fig8_cyclic_blocked.py) — Fig 8: cyclic vs blocked edge
  deal inside the LB executor (XLA and Pallas paths) + the Fig 4
  structural locality metric.
* ``fig9``     (fig9_partition.py)      — Fig 9: OEC/IEC/CVC partition
  policies (edge balance, mirrors, round counts).
* ``qps``      (fig_qps.py)             — batched multi-source query
  throughput: queries/sec of bfs_batch/sssp_batch vs batch size on the
  power-law input (DESIGN.md section 7); ``--smoke`` variant gates CI.
* ``serve``    (fig_serve.py)           — continuous-batching service
  throughput/latency vs the restart-per-batch baseline: Zipf traffic
  with the LRU cache + single-flight coalescing, Poisson-arrival
  latency sweep, deterministic slot-packing comparison (DESIGN.md
  section 8); ``--smoke`` variant gates CI.
* ``direction`` (fig_direction.py)      — push vs pull vs adaptive
  traversal direction per round (DESIGN.md section 9): wall clock,
  round counts, adaptive's pull share; ``--smoke`` gates parity and
  the adaptive direction trace structurally (no timing gate).
* ``update``   (fig_update.py)          — streaming edge updates:
  incremental label repair vs full recompute, rounds and wall clock
  per update on insert-only and mixed traces (DESIGN.md section 10);
  ``--smoke`` gates incremental/full parity and that insert-only
  repair rounds never exceed full-recompute rounds (no timing gate).
* ``fused``    (fig_fused.py)           — device-resident planning
  (DESIGN.md section 11): host vs fused round loops per app x graph;
  ``--smoke`` gates fused/host label parity, ``host_transfers == 0``
  per fused traversal, and the on-device direction trace against the
  host threshold rule replayed over device-recorded counts (no
  timing gate).
* ``fleet``    (fig_fleet.py)           — multi-replica serving fleet
  (DESIGN.md section 13): rendezvous-affinity hit rate vs the pure-P2C
  ablation, bounded-load ceiling audit, hedging under forced
  stragglers, and bitwise routing-trace replay; all gates structural
  (no timing gate), enforced at every scale.
* ``roofline`` (roofline.py)            — kernel roofline estimates
  from dry-run artifacts (skipped when artifacts are absent).

``-h``/``--help`` prints this selector table; an unknown selector is
an error (exit 2), not a silent no-op.

All inputs are synthetic analogues of the paper's graph classes (see
benchmarks/common.py: rmat = power-law, road = grid, uniform = flat).
"""
from __future__ import annotations

import sys


SELECTORS = ("table2", "fig6", "fig8", "fig9", "qps", "serve",
             "direction", "update", "fused", "fleet", "roofline")


def main() -> None:
    argv = sys.argv[1:]
    if "-h" in argv or "--help" in argv:
        print(__doc__)
        return
    unknown = [a for a in argv if a not in SELECTORS]
    if unknown:
        print(f"unknown selector(s): {', '.join(sorted(unknown))}\n"
              f"valid selectors: {', '.join(SELECTORS)} "
              f"(see --help)", file=sys.stderr)
        sys.exit(2)
    which = set(argv) or set(SELECTORS)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    if "table2" in which:
        from . import table2_strategies
        table2_strategies.run()
    if "fig6" in which:
        from . import fig6_scaling
        fig6_scaling.run()
    if "fig8" in which:
        from . import fig8_cyclic_blocked
        fig8_cyclic_blocked.run()
    if "fig9" in which:
        from . import fig9_partition
        fig9_partition.run()
    if "qps" in which:
        from . import fig_qps
        fig_qps.run()
    if "serve" in which:
        from . import fig_serve
        fig_serve.run()
    if "direction" in which:
        from . import fig_direction
        if fig_direction.run():
            # structural gate failures (parity / adaptive trace) must
            # fail the aggregate run too, not just the --smoke entry
            sys.exit(1)
    if "update" in which:
        from . import fig_update
        if fig_update.run():
            # parity between incremental repair and full recompute is
            # a correctness property — fail the aggregate run
            sys.exit(1)
    if "fused" in which:
        from . import fig_fused
        if fig_fused.run():
            # fused/host parity and the zero-sync property are
            # correctness properties — fail the aggregate run
            sys.exit(1)
    if "fleet" in which:
        from . import fig_fleet
        if fig_fleet.run():
            # routing replay, the bounded-load ceiling, and hedge
            # publish-once/parity are correctness properties — fail
            # the aggregate run
            sys.exit(1)
    if "roofline" in which:
        from . import roofline
        try:
            roofline.main()
        except Exception as e:       # artifacts may not exist yet
            print(f"roofline,0,skipped ({e})")


if __name__ == "__main__":
    main()
