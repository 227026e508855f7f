"""Bring-up check of the graph engine on TPU: its main path, once, at
full size, through the entry points a user calls.

One chip (the default):

1. generate the Graph500 Kronecker graph (``G.rmat`` at its defaults:
   a=.57, b=c=.19, edge factor 16) at scale 22 and place it on the chip;
2. run ``apps.bfs`` and ``apps.sssp`` (host mode, ``strategy="alb"``)
   from the highest-out-degree vertex and two more sources under the
   ``xla``, ``pallas`` and ``merge_path`` backends;
3. serve Zipf-drawn BFS/SSSP queries through
   ``QueryService(num_slots=8)`` under ``backend="merge_path"``,
   repeats answered from its cache.

``--chips 4`` runs only the partitioned path on four chips: the same
graph cut four ways under ``oec`` and ``cvc``, traversed by
``gluon.bfs_distributed`` / ``sssp_distributed`` with ``sync="mirror"``.

Every label array is compared bitwise with ``scipy.sparse.csgraph``,
which shares no code with the engine.  Any failure raises, so the run
exits non-zero.  The last line, ``{"ok": true, "device": {...}}``, is
printed only on a TPU, at full scale, after every check passed.  On
any other platform the script stops at once, unless ``--scale`` asks
for a rehearsal: that runs every phase and then exits 1.

  python chip_smoke.py                     # one chip
  python chip_smoke.py --chips 4           # four chips, mirror sync
  JAX_PLATFORMS=cpu python chip_smoke.py --scale 10       # rehearsal
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      python chip_smoke.py --chips 4 --scale 10
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

FULL_SCALE = 22
EDGE_FACTOR = 16
NUM_SLOTS = 8
# popularity exponent of the service's sources over out-degree rank
ZIPF_A = 1.2
# jax.monitoring's event for one XLA compilation (tracing and lowering
# events nest inside each other, so they are not summed)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds spent in XLA compilations, and their number, as reported
    by ``jax.monitoring``."""

    def __init__(self, jax):
        self.seconds, self.compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.seconds += duration
            self.compiles += 1


class Phase:
    """Times one phase: wall seconds, the XLA compile seconds inside
    them, the rest (run), compilations, and the device's peak bytes so
    far."""

    def __init__(self, clock, device, name):
        self.clock, self.device, self.name = clock, device, name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.n0 = self.clock.seconds, self.clock.compiles
        return self

    def __exit__(self, *exc):
        if exc[0] is not None:
            return False
        self.wall = time.perf_counter() - self.t0
        self.compile = self.clock.seconds - self.c0
        stats = self.device.memory_stats() or {}
        self.report = (f"wall_s={self.wall:.3f} "
                       f"xla_compile_s={self.compile:.3f} "
                       f"run_s={self.wall - self.compile:.3f} "
                       f"compiles={self.clock.compiles - self.n0} "
                       f"peak_bytes={stats.get('peak_bytes_in_use', 'n/a')}")
        return False


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class Reference:
    """BFS levels and SSSP distances from ``scipy.sparse.csgraph`` over
    a host copy of the CSR, as int32 labels with the engine's INF for
    unreachable vertices."""

    def __init__(self, g, inf):
        import scipy.sparse
        rp, ci, w = (np.asarray(a) for a in (g.row_ptr, g.col_idx,
                                             g.edge_w))
        v = len(rp) - 1
        self.adj = scipy.sparse.csr_matrix(
            (w.astype(np.float64), ci, rp), shape=(v, v))
        self.inf, self.memo = inf, {}

    def labels(self, app: str, source: int) -> np.ndarray:
        from scipy.sparse import csgraph
        key = (app, source)
        if key not in self.memo:
            d = csgraph.shortest_path(self.adj, indices=source,
                                      unweighted=app == "bfs")
            self.memo[key] = np.where(np.isinf(d), self.inf,
                                      d).astype(np.int32)
        return self.memo[key]

    def match(self, app, source, labels, what):
        want = self.labels(app, source)
        got = np.asarray(labels)
        bad = int(np.count_nonzero(got != want))
        check(got.shape == want.shape and bad == 0,
              f"{what}: {bad} labels differ from scipy.sparse.csgraph")
        return int(np.count_nonzero(want < self.inf))


class ProgramLog:
    """The programs a phase ran, read back from JAX's own IR dump
    (``jax_dump_ir_to``): JAX writes the StableHLO of every program it
    compiles, or fetches from the persistent cache, while the dump
    directory is set."""

    def __init__(self, jax, root):
        self.jax, self.root, self.n = jax, root, 0

    def __enter__(self):
        self.n += 1
        self.dir = os.path.join(self.root, f"phase{self.n}")
        os.makedirs(self.dir)
        self.jax.config.update("jax_dump_ir_to", self.dir)
        return self

    def __exit__(self, *exc):
        self.jax.config.update("jax_dump_ir_to", "")
        return False

    def kernels(self, entries) -> tuple:
        """``(with_kernel, programs)``: how many of the phase's
        programs named ``jit_<entry>`` lower a Mosaic kernel
        (``tpu_custom_call``), and how many there are."""
        names = tuple(f"_jit_{e}_compile.mlir" for e in entries)
        texts = []
        for f in sorted(os.listdir(self.dir)):
            if f.endswith(names):
                with open(os.path.join(self.dir, f)) as fh:
                    texts.append(fh.read())
        return sum("tpu_custom_call" in t for t in texts), len(texts)


def kernels_ran_compiled(log, entries, platform, what) -> str:
    """Checks that the phase ran programs with the kernels in them and,
    on a TPU, that every one of them lowered the kernels with Mosaic."""
    hit, total = log.kernels(entries)
    check(total > 0, f"{what}: no program of {entries} ran")
    check(hit == total or platform != "tpu",
          f"{what}: {total - hit} of {total} kernel programs lowered "
          f"without tpu_custom_call")
    return f"pallas_compiled={hit == total} kernel_programs={hit}/{total}"


def pick_sources(g, count: int, seed: int) -> list:
    """The highest-out-degree vertex, then distinct random vertices
    with out-degree > 0."""
    deg = np.asarray(g.out_degrees())
    hub = int(np.argmax(deg))
    rng = np.random.default_rng(seed)
    rest = [int(s) for s in rng.permutation(np.flatnonzero(deg > 0))
            if s != hub]
    return [hub] + rest[:count - 1]


def service_traffic(g, seed: int) -> tuple:
    """The service's queries, as two waves: sources drawn from a Zipf
    law (exponent ``ZIPF_A``) over the vertices with out-edges ranked
    by out-degree, restricted to the first ``NUM_SLOTS`` distinct
    sources drawn, until there are ``2 * NUM_SLOTS`` draws.  The first
    wave holds one query per distinct source, the second every repeat,
    submitted once the first is answered, so the repeats are served
    from the result cache.  Each source keeps one app, bfs and sssp in
    turn by first draw."""
    deg = np.asarray(g.out_degrees())
    live = np.flatnonzero(deg > 0)
    ranked = live[np.argsort(-deg[live], kind="stable")]
    ranks = np.random.default_rng(seed).zipf(ZIPF_A, 64 * NUM_SLOTS) - 1
    app_of, draws = {}, []
    for r in ranks[ranks < len(ranked)]:
        s = int(ranked[r])
        if s not in app_of:
            if len(app_of) == NUM_SLOTS:
                continue
            app_of[s] = ("bfs", "sssp")[len(app_of) % 2]
        draws.append(s)
        if len(app_of) == NUM_SLOTS and len(draws) >= 2 * NUM_SLOTS:
            break
    else:
        raise RuntimeError("Zipf draws never held enough distinct "
                           "sources")
    first = [(app_of[s], s) for s in app_of]
    repeats = list(draws)
    for s in app_of:
        repeats.remove(s)
    return first, [(app_of[s], s) for s in repeats]


# the jitted entries, per backend, whose programs hold the kernels
_KERNEL_ENTRIES = {"pallas": ("twc_bin_apply", "edge_lb_apply"),
                   "merge_path": ("merge_path_apply",)}
# gluon.make_mirror_round_fn's jitted shard_map round
_MIRROR_ROUND_ENTRIES = ("round_fn",)


def single_chip(jax, g, ref, clock, dev, seed, programs):
    from repro.core.apps import bfs, sssp
    from repro.core.balancer import BalancerConfig
    from repro.serve.engine import QueryService

    sources = pick_sources(g, 3, seed)
    waves = service_traffic(g, seed + 1)
    t0 = time.perf_counter()
    for app in ("bfs", "sssp"):
        for s in sources:
            ref.labels(app, s)
    for wave in waves:
        for app, s in wave:
            ref.labels(app, s)
    print(f"setup reference_s={time.perf_counter() - t0:.3f} "
          f"traversals={len(ref.memo)}", flush=True)

    apps = {"bfs": bfs, "sssp": sssp}
    for backend in ("xla", "pallas", "merge_path"):
        cfg = BalancerConfig(strategy="alb", backend=backend)
        for app, run in apps.items():
            with Phase(clock, dev, f"{app}/{backend}") as ph, programs:
                outs = [run(g, s, cfg) for s in sources]
                labels = [np.asarray(o.labels) for o in outs]
            reached = [ref.match(app, s, lab, f"{app}/{backend}/src={s}")
                       for s, lab in zip(sources, labels)]
            rounds = [o.rounds for o in outs]
            kernels = ("pallas_compiled=n/a" if backend == "xla" else
                       kernels_ran_compiled(programs,
                                            _KERNEL_ENTRIES[backend],
                                            dev.platform,
                                            f"{app}/{backend}"))
            print(f"phase {app}/{backend} strategy=alb mode=host "
                  f"sources={sources} rounds={rounds} "
                  f"reached={reached} {ph.report} {kernels} "
                  f"labels=bitwise-equal", flush=True)

    # the plan-free backend: an alb bin pass pads each frontier edge to
    # about four slots, and every slot is gathered and scattered for
    # all eight rows of the bank
    svc_cfg = BalancerConfig(strategy="alb", backend="merge_path")
    svc = QueryService(num_slots=NUM_SLOTS, cfg=svc_cfg)
    svc.register_graph("graph500", g)
    with Phase(clock, dev, "service") as ph:
        qids = []
        for wave in waves:
            for app, s in wave:
                qids.append((svc.submit("graph500", app, s), app, s))
            svc.run()
    cached = 0
    for qid, app, s in qids:
        q = svc.poll(qid)
        check(q.status == "done", f"service query {qid} {q.status}")
        ref.match(app, s, q.result, f"service/{app}/src={s}")
        cached += bool(q.from_cache)
    st = svc.stats
    distinct = len({s for _, _, s in qids})
    print(f"phase service slots={NUM_SLOTS} backend={svc_cfg.backend} "
          f"queries={len(qids)} "
          f"distinct_sources={distinct} zipf_a={ZIPF_A} "
          f"from_cache={cached} computed={st.cache_misses} "
          f"steps={st.steps} occupancy={st.occupancy:.3f} "
          f"host_transfers={st.host_transfers} {ph.report} "
          f"labels=bitwise-equal", flush=True)
    check(len(qids) >= 2 * NUM_SLOTS and distinct >= NUM_SLOTS,
          f"service traffic: {len(qids)} queries, {distinct} sources")
    check(cached == len(waves[1]) > 0,
          f"{cached} of {len(waves[1])} repeats answered from the cache")


def four_chips(jax, g, ref, clock, dev, chips, programs):
    from repro.core import gluon
    from repro.core.balancer import BalancerConfig
    from repro.core.graph import highest_out_degree_vertex
    from repro.core.partition import partition

    mesh = gluon.device_mesh(chips)
    src = highest_out_degree_vertex(g)
    # merge_path: the spmd round's bins run at capacity V, which at
    # this V does not fit (ROADMAP S1); the plan-free backend does
    cfg = BalancerConfig(backend="merge_path")
    t0 = time.perf_counter()
    for app in ("bfs", "sssp"):
        ref.labels(app, src)
    print(f"setup reference_s={time.perf_counter() - t0:.3f} "
          f"traversals={len(ref.memo)}", flush=True)
    for policy in ("oec", "cvc"):
        t0 = time.perf_counter()
        sg, meta = partition(g, chips, policy)
        jax.block_until_ready(sg.col_idx)
        print(f"setup partition={policy} parts={chips} "
              f"seconds={time.perf_counter() - t0:.3f} "
              f"local_edges={sg.col_idx.shape[-1]} "
              f"mirror_slots={meta.mirror_idx.shape[-1]}", flush=True)
        for app, run in (("bfs", gluon.bfs_distributed),
                         ("sssp", gluon.sssp_distributed)):
            with Phase(clock, dev, f"{app}/{policy}") as ph, programs:
                labels, rounds, _ = run(sg, mesh, src, cfg,
                                        sync="mirror", meta=meta)
                labels = np.asarray(labels)
            reached = ref.match(app, src, labels, f"{app}/{policy}/mirror")
            kernels = kernels_ran_compiled(programs, _MIRROR_ROUND_ENTRIES,
                                           dev.platform,
                                           f"{app}/{policy}")
            print(f"phase {app}/{policy} sync=mirror backend=merge_path "
                  f"mode=host source={src} rounds={rounds} "
                  f"reached={reached} {ph.report} {kernels} "
                  f"labels=bitwise-equal", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--scale", type=int, default=None,
                    help="rehearsal scale (default: 22, TPU only)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import scipy
    from repro.compile_cache import enable_compile_cache
    from repro.core import graph as G

    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and args.scale is None:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform}); "
              f"pass --scale N to rehearse on it", file=sys.stderr)
        return 2
    scale = FULL_SCALE if args.scale is None else args.scale
    cache = enable_compile_cache()
    clock = CompileClock(jax)
    print(f"versions python={platform.python_version()} "
          f"jax={jax.__version__} numpy={np.__version__} "
          f"scipy={scipy.__version__}", flush=True)
    print(f"devices {[f'{d.platform}:{d.device_kind}:{d.id}' for d in devices]}"
          f" compile_cache={cache}", flush=True)

    t0 = time.perf_counter()
    g = G.rmat(scale, EDGE_FACTOR, seed=args.seed)
    jax.block_until_ready((g.row_ptr, g.col_idx, g.edge_w))
    t_graph = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = Reference(g, int(G.INF))
    print(f"setup graph=rmat scale={scale} edge_factor={EDGE_FACTOR} "
          f"seed={args.seed} V={g.num_vertices} E={g.num_edges} "
          f"on={g.col_idx.devices()} generate_and_place_s={t_graph:.3f} "
          f"reference_csr_s={time.perf_counter() - t0:.3f}", flush=True)

    with tempfile.TemporaryDirectory() as ir_dir:
        programs = ProgramLog(jax, ir_dir)
        if args.chips == 1:
            single_chip(jax, g, ref, clock, dev, args.seed, programs)
        else:
            four_chips(jax, g, ref, clock, dev, args.chips, programs)
    peak = dev.memory_stats() or {}
    print(f"done xla_compile_s={clock.seconds:.3f} "
          f"compiles={clock.compiles} "
          f"peak_bytes={peak.get('peak_bytes_in_use', 'n/a')}", flush=True)

    if not (on_tpu and scale == FULL_SCALE):
        print(f"chip_smoke: rehearsal on {dev.platform} at scale {scale} "
              f"passed; only a TPU run at scale {FULL_SCALE} reports ok",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
