"""The check that decides ``correct``, on the CPU at small sizes: a run
of the timed path passes it, and a run with the timed path broken
underneath fails it, for each fault a one-chip traversal cell can have.
The control (the traversal stopped before its deepest level) fails it
too, the warm-up leaves nothing to build in the window, and the seed
renames the roots."""
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                if p not in sys.path]

from bench import control, harness  # noqa: E402
from repro.core import apps  # noqa: E402
from repro.core.apps import drivers  # noqa: E402

# each cell at a size a test run holds
SMALL = {"rmat-22.bfs": dict(scale=9)}


def small_cell(name):
    cell = harness.load_cell(name)
    cell.config["params"].update(SMALL[name])
    return cell


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    # the run's persistent cache would stay on for the rest of the process
    monkeypatch.setattr(harness, "use_compile_cache", lambda jax: None)


def run(name, seed=11):
    result, checks = harness.run_cell(small_cell(name), seed, 0.2, False,
                                      time.perf_counter(),
                                      require_accelerator=False)
    assert list(result)[-1] == "checks"
    assert result["checks"] == checks
    return result


def _round_unchanged(orig):
    def round_(g, values, labels, frontier, *a, **kw):
        _, st, active = orig(g, values, labels, frontier, *a, **kw)
        return labels, st, active
    return round_


def _round_half_frontier(orig):
    def round_(g, values, labels, frontier, *a, **kw):
        keep = jnp.arange(frontier.shape[-1]) % 2 == 0
        return orig(g, values, labels, frontier & keep, *a, **kw)
    return round_


def _answer_altered(orig):
    def app(*a, **kw):
        out = orig(*a, **kw)
        lab = out.labels
        deepest = jnp.argmax(jnp.where(lab < harness.UNREACHED, lab, -1))
        out.labels = lab.at[deepest].add(1)
        return out
    return app


FAULTS = {
    "step_returns_state_unchanged": (drivers, "_round", _round_unchanged),
    "half_of_frontier_left_out": (drivers, "_round", _round_half_frontier),
    "answer_altered_where_produced": (apps, "bfs", _answer_altered),
}


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 11, 2 ** 33 + 1])
@pytest.mark.parametrize("name", sorted(SMALL))
def test_sound_run_is_correct(name, seed):
    result = run(name, seed)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["checks"]["mismatched_labels"] == {"value": 0, "limit": 0}
    assert set(result["metrics"]) == {"teps", "setup_s"}
    assert result["metrics"]["teps"]["value"] > 0


@pytest.mark.parametrize("seed", [11, 2 ** 32 + 11])
@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", sorted(SMALL))
def test_fault_makes_run_incorrect(name, fault, seed, monkeypatch):
    module, attr, wrap = FAULTS[fault]
    monkeypatch.setattr(module, attr, wrap(getattr(module, attr)))
    result = run(name, seed)
    assert result["correct"] is False
    assert result["failed"] > 0
    assert result["checks"]["mismatched_labels"]["value"] > 0


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_fails_and_program_passes(name):
    for seed in (3, 2 ** 31 + 3):
        r = control.readings(small_cell(name), seed)
        assert r["program"] == [0] * len(r["program"])
        assert min(r["control"]) > 0


@pytest.mark.parametrize("name", sorted(SMALL))
def test_warmup_builds_every_program_the_cycle_runs(name):
    cell = small_cell(name)
    clock = harness.CompileClock(jax)
    built = harness.build(cell, 5)
    for root in built.cycle:
        built.traverse(root)
    before = clock.builds
    for root in built.cycle:
        built.traverse(root)
    assert clock.builds == before


@pytest.mark.parametrize("name", sorted(SMALL))
def test_seed_renames_the_roots(name):
    """Every seed traverses from the same roots of the configuration's
    graph, under the seed's names, all of out-degree >= 1."""
    from bench.generators import kronecker
    cell = small_cell(name)
    params = cell.config["params"]
    base, named = set(), set()
    for seed in (5, 6, 2 ** 31 + 5):
        built = harness.build(cell, seed)
        assert len(built.cycle) == cell.traffic["roots"]["strata"]
        assert all(built.out_degree[v] > 0 for v in built.cycle)
        inv = np.argsort(kronecker.relabelling(params, seed))
        base.add(tuple(int(inv[v]) for v in built.cycle))
        named.add(tuple(built.cycle))
    assert len(base) == 1 and len(named) == 3


@pytest.mark.parametrize("chips", [2, 4])
def test_cell_on_more_chips_is_refused(chips):
    """The timed path is the one-chip apps': a cell on more chips raises
    rather than run on one and report several."""
    cell = small_cell("rmat-22.bfs")
    cell.chips = chips
    with pytest.raises(ValueError, match="one-chip cells only"):
        harness.build(cell, 5)
