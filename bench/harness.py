"""One run of one cell: set-up, warm-up, the measured window, the check
against the reference, and the result's line.

Everything that belongs to one configuration, traffic mix, layer or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the graph's generator and its parameters,
  the ``BalancerConfig`` fields and the round mode;
- ``traffic/<traffic>.json``: the app, the root sampler, its
  parameters and the seed it draws from, and how many traversals a
  traced run holds;
- ``generators/<name>.py``, ``samplers/<name>.py``: the code those name;
- ``layers/<layer>.json``: the programs whose device time is a layer's;
- ``metrics/<metric>.py``: the reader of one per-layer metric.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
import sys
import tempfile
import time

import numpy as np

from bench import trace as trace_mod
from bench import work
from bench.csr import UNREACHED
from bench.reference import Reference

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# JAX's persistent compilation cache: one fixed directory inside the
# checkout, so that only a cell's first run there compiles
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# jax.monitoring's event around each program built for a jit cache miss
# (an XLA compilation or a load from the persistent cache)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# host span around each traversal, read back from the trace
TRAVERSAL_SPAN = "bench.traversal"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_file: str = os.path.join(ROOT,
                                                       "BENCHMARK.json")
              ) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files read."""
    spec = _load_json(bench_file)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_file}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic",
                                      w["traffic"] + ".json"))

    def here(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name, w["chips"], config, traffic,
                [m for m in spec["end_to_end"] if here(m)],
                [m for m in spec["per_layer"] if here(m)])


def load_layers() -> dict:
    """``{layer file name: [program names]}`` from ``layers/``."""
    d = os.path.join(BENCH_DIR, "layers")
    return {f[:-5]: _load_json(os.path.join(d, f))["programs"]
            for f in sorted(os.listdir(d)) if f.endswith(".json")}


def load_peaks(device_kind: str) -> dict:
    peaks = _load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return peaks[device_kind]


class CompileClock:
    """Programs built for jit cache misses (compiled, or loaded from the
    persistent cache), counted by ``jax.monitoring``."""

    def __init__(self, jax):
        self.builds = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.builds += 1


@dataclasses.dataclass
class Traversal:
    root: int
    labels: object        # device array until the window closes
    rounds: int
    seconds: float


def devices(jax, chips: int, require_accelerator: bool = True):
    """The chips of the run; raises :class:`NoAccelerator`."""
    found = jax.devices()
    if require_accelerator and found[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{found[0].platform})")
    if len(found) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(found)}")
    return found[:chips]


def use_compile_cache(jax) -> None:
    """JAX's persistent compilation cache in :data:`CACHE_DIR`, for
    every program whatever its compile time or size, and with no
    eviction (whatever the environment sets: a size limit there made
    writes fail on a TPU v5e host, and nothing was cached)."""
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def start_trace(jax, trace_dir: str) -> None:
    """The profiler on, without its Python tracer, whose cost per
    Python call would swell the host's share of each round."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def _roots(cell: Cell, generator, out_degree, seed: int) -> list:
    """The cycle: roots drawn in the configuration's graph from the
    traffic's own fixed seed, then renamed by the run's relabelling, so
    that every seed does the same work (drawn from the run's seed, the
    roots spread a cell's rate over seeds twenty times as widely as
    over runs of one seed)."""
    spec = cell.traffic["roots"]
    sampler = importlib.import_module(f"bench.samplers.{spec['sampler']}")
    perm = generator.relabelling(cell.config["params"], seed)
    base = sampler.sample(spec, out_degree=out_degree[perm],
                          rng=np.random.default_rng(spec["seed"]))
    return [int(perm[r]) for r in base]


@dataclasses.dataclass
class Built:
    """A cell's graph on the device, its root cycle, and the timed
    path: ``traverse(root, **kw)`` runs the cell's app from ``root``
    through ``repro.core.apps`` and waits for its labels."""
    graph: object
    out_degree: np.ndarray
    cycle: list
    app: str
    traverse: object

    def reference(self) -> Reference:
        """The plain reference over a host copy of the graph."""
        g = self.graph
        return Reference(*(np.asarray(a)
                           for a in (g.row_ptr, g.col_idx, g.edge_w)))


def build(cell: Cell, seed: int) -> Built:
    """The cell's graph, made on the device from ``seed`` in one jitted
    call, and its roots, renamed by ``seed``."""
    if cell.chips > 1:
        # the timed path below is the one-chip apps'; a cell on more
        # chips needs a dispatch to the partitioned path first
        raise ValueError(f"{cell.name}: the harness runs one-chip cells "
                         f"only, the cell asks for {cell.chips} chips")
    import jax
    from repro.core import apps
    from repro.core.balancer import BalancerConfig
    from repro.core.graph import Graph

    generator = importlib.import_module(
        f"bench.generators.{cell.config['generator']}")
    g = Graph(*generator.generate(cell.config["params"], seed))
    jax.block_until_ready(g)
    out_degree = np.diff(np.asarray(g.row_ptr))
    cycle = _roots(cell, generator, out_degree, seed)
    app = cell.traffic["app"]
    cfg = BalancerConfig(**cell.config["balancer"])
    mode = cell.config["mode"]

    def traverse(root: int, **kw) -> Traversal:
        t0 = time.perf_counter()
        # looked up per call, so that a test can put a fault in its place
        out = getattr(apps, app)(g, root, cfg, mode=mode, **kw)
        jax.block_until_ready(out.labels)
        return Traversal(root, out.labels, out.rounds,
                         time.perf_counter() - t0)

    return Built(g, out_degree, cycle, app, traverse)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_accelerator: bool = True):
    """One run; returns ``(result, checks)``: the result's line as a
    dict (``checks`` last in it) and the numbers compared with their
    limits."""
    import jax

    chips = devices(jax, cell.chips, require_accelerator)
    use_compile_cache(jax)
    clock = CompileClock(jax)

    built = build(cell, seed)
    out_degree, cycle, app = built.out_degree, built.cycle, built.app
    # one pass over the cycle builds every shape bucket the window uses
    for root in cycle:
        built.traverse(root)
    setup_s = time.perf_counter() - t_start

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as trace_dir:
        runs = []
        builds0 = clock.builds
        if trace:
            start_trace(jax, trace_dir)
        t0 = time.perf_counter()
        while True:
            root = cycle[len(runs) % len(cycle)]
            if trace:
                with jax.profiler.TraceAnnotation(TRAVERSAL_SPAN):
                    runs.append(built.traverse(root))
                if len(runs) == cell.traffic["trace_traversals"]:
                    break
            else:
                runs.append(built.traverse(root))
                if time.perf_counter() - t0 >= seconds:
                    break
        window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
            reduced = trace_mod.reduce_dir(trace_dir, TRAVERSAL_SPAN,
                                           len(chips))
        compiles_in_window = clock.builds - builds0

    stats = [d.memory_stats() or {} for d in chips]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    # the program's state is freed before the reference runs
    for r in runs:
        r.labels = np.asarray(r.labels)
    ref = built.reference()
    del built
    mismatched, failed, scanned, least = 0, 0, 0, 0
    for r in runs:
        want = ref.labels(app, r.root)
        got = r.labels
        bad = (int(np.count_nonzero(got != want))
               if got.shape == want.shape else want.size)
        mismatched += bad
        failed += bad > 0
        reached = want < UNREACHED
        edges = work.scanned_edges(out_degree, reached)
        scanned += edges
        least += work.least_bytes(app, out_degree, reached)
        print(f"traversal root={r.root} rounds={r.rounds} edges={edges} "
              f"seconds={r.seconds:.4f} mismatched={bad}", file=sys.stderr)

    device = {"platform": chips[0].platform,
              "kind": chips[0].device_kind, "count": len(chips),
              "memory_peak_bytes": memory_peak}
    result = {"correct": failed == 0 and len(runs) > 0,
              "attempted": len(runs), "failed": failed}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        ctx = MetricContext(
            trace=reduced, layers=load_layers(),
            peaks=load_peaks(chips[0].device_kind),
            traversals=len(runs), rounds=sum(r.rounds for r in runs),
            least_bytes=least, compiles_in_window=compiles_in_window)
        values = {}
        for m in cell.per_layer:
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            v = reader.read(ctx)
            if v is not None:
                values[m["name"]] = v
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    else:
        values = {"teps": scanned / window_s, "setup_s": setup_s}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in values.items() if k in units}
    result["device"] = device
    checks = {"mismatched_labels": {"value": mismatched, "limit": 0}}
    result["checks"] = checks
    return result, checks


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric's reader reads: the reduced trace of the
    traced traversals, the layer table, the device's peaks, and the
    traversals' own counts."""
    trace: "trace_mod.Reduced"
    layers: dict
    peaks: dict
    traversals: int
    rounds: int
    least_bytes: int
    compiles_in_window: int

    def layer_seconds(self, *layer_files: str):
        """Device seconds, per chip, of the programs of these layers;
        None where none of them ran."""
        names = [p for f in layer_files for p in self.layers[f]]
        return self.trace.program_seconds(names)
