"""The readings that the limit of ``correct`` is set from: the timed
path's label mismatches against the plain reference (the lower reading,
0 on a sound run) and the control's (the upper reading).

The configurations state no precision; the control breaks the guarantee
they state, exact labels for every vertex the root reaches: it is the
timed path stopped two rounds before its frontier empties
(``max_rounds``), so the deepest level goes unlabelled, as an early exit
that a later change might be tempted by would leave it.

    python3 bench/control.py --workload rmat-22.bfs --seeds 11 12 13

For each seed it builds the cell's graph and root cycle as a run does,
traverses every root of the cycle through the timed path and through
the control, and prints one line a seed; the last line is a JSON
summary.  Not part of a benchmark run.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def readings(cell, seed: int, roots: int = 0) -> dict:
    """``{"program": [...], "control": [...]}``: mismatched labels per
    root of the seed's cycle (its first ``roots``, if not 0)."""
    import numpy as np
    from bench import harness

    built = harness.build(cell, seed)
    program, control = [], []
    ref = built.reference()
    for root in built.cycle[:roots or None]:
        run = built.traverse(root)
        cut = built.traverse(root, max_rounds=max(run.rounds - 2, 0))
        want = ref.labels(built.app, root)
        program.append(int(np.count_nonzero(np.asarray(run.labels) != want)))
        control.append(int(np.count_nonzero(np.asarray(cut.labels) != want)))
    return {"program": program, "control": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--roots", type=int, default=0,
                    help="traverse only the first N roots of each cycle")
    args = ap.parse_args(argv)

    import jax
    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.use_compile_cache(jax)
    harness.devices(jax, cell.chips, require_accelerator=True)
    lower, upper = 0, None
    for seed in args.seeds:
        r = readings(cell, seed, args.roots)
        print(f"seed={seed} program={r['program']} control={r['control']}",
              flush=True)
        lower = max(lower, max(r["program"]))
        low = min(r["control"])
        upper = low if upper is None else min(upper, low)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "lower": lower, "upper": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
