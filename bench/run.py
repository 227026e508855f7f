"""Runs one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload rmat-22.bfs --seed 7 --seconds 30 --trace 0

Loads the cell's graph and roots from the seed, warms up every shape
its traversals use, measures for ``--seconds`` (whole traversals back
to back), checks every traversal's labels against the plain reference
and prints the result as the last line of standard output: the
end-to-end metrics with ``--trace 0``, the per-layer metrics (from a
profiler trace of the cell's traced traversals) with ``--trace 1``.
The numbers compared, each with its limit, are the last lines of
standard error and the ``checks`` key of the result.  Without a TPU,
or with fewer chips than the cell asks for, it prints no result and
exits 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the benchmark's modules are imported as the package ``bench``, the
# system under test from ``src``
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    try:
        result, checks = harness.run_cell(cell, args.seed, args.seconds,
                                          bool(args.trace), T_START)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    for name, c in checks.items():
        print(f"check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
