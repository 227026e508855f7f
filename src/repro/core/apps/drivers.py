"""The paper's five applications: bfs, sssp, cc, pagerank, kcore.

Each driver runs the data-driven round structure of Section 2.1:
process the *current* worklist, collect the *next* worklist from label
changes, repeat until empty.  All of them are thin wrappers over the
balancer round so every application automatically benefits from
whichever load-balancing strategy is configured — the compiler-level
reuse the paper gets from IrGL.

``mode`` selects the round implementation (DESIGN.md sections 3, 11):

* ``"host"`` — ``balancer.relax``: per-round host decisions + bucketed
  jit shapes (the single-device wall-clock configuration);
* ``"spmd"`` — ``balancer.relax_spmd``: the fully-jit static-capacity
  round used inside ``shard_map`` by the distributed runtime, here run
  on one device so its behaviour (including the jit-safe RoundStats)
  can be measured and tested against the host round;
* ``"fused"`` — ``balancer.run_fused``: the whole traversal as ONE
  ``lax.while_loop`` with the inspector and the direction rule on
  device — zero host syncs between the initial dispatch and the final
  label fetch (``AppResult.host_transfers == 0``).  Labels, rounds,
  and per-round stats are bitwise those of ``"host"`` mode.

``bfs_batch`` / ``sssp_batch`` serve B independent sources from ONE
shared convergence loop (DESIGN.md section 7): labels and frontier
carry a ``[B, V]`` batch axis, every balancer round plans over the
union frontier, and a finished query retires itself — its frontier row
empties, so it stops contributing vertices to the union while the loop
drains the remaining queries.  The loop ends when the union is empty,
and each query's labels are bitwise what its own single-source run
would have produced.

The continuous-batching service (``repro.serve``, DESIGN.md section 8)
builds on the same round structure through two public hooks here:
:func:`relax_round` (one balancer round in either execution mode) and
:func:`step_batch` (round + min-combine frontier update over ``[B, V]``
slot state), plus the :data:`QUERY_APPS` registry naming the
point-query applications a service can admit.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..graph import Graph, INF
from ..frontier import full_frontier, single_source, multi_source_state
from ..balancer import (BalancerConfig, RoundStats, relax,
                        relax_spmd_directed, relax_fused_round,
                        run_fused, fused_stats_host,
                        host_transfer_count, _fused_stats_init,
                        _note_host_transfer)
from .. import operators as ops


@dataclasses.dataclass
class AppResult:
    """What every driver returns: final labels, round count, wall-clock
    seconds, (with ``collect_stats=True``) per-round
    :class:`RoundStats`, and the number of blocking device->host sync
    points the traversal's round loop performed (0 in fused mode —
    the assertable form of the zero-sync property, DESIGN.md
    section 11)."""
    labels: jax.Array
    rounds: int
    seconds: float
    stats: Optional[List[RoundStats]] = None
    host_transfers: int = 0


def relax_round(g, values, labels, frontier, cfg, op,
                collect_stats=False, mode="host",
                return_active=False):
    """One balancer round in the selected execution mode (``"host"`` |
    ``"spmd"``); always returns (labels, RoundStats|None) with
    host-side stats.  The single round primitive shared by every driver
    loop here and by the serving engine (DESIGN.md section 8).

    Both modes honour ``cfg.direction`` (DESIGN.md section 9): the
    host round resolves it inside :func:`repro.core.balancer.relax`,
    the spmd round through
    :func:`repro.core.balancer.relax_spmd_directed`.

    ``return_active=True`` appends a host ``bool[B]`` per-row liveness
    vector (``bool[1]`` un-batched) — in host mode it is sliced from
    the fused count transfer the round already pays, so the driver
    loops can converge without issuing a separate blocking
    ``jnp.any(frontier)`` every round."""
    if mode == "host":
        return relax(g, values, labels, frontier, cfg, op,
                     collect_stats=collect_stats,
                     return_active=return_active)
    if mode != "spmd":
        raise ValueError(f"unknown round mode {mode!r} (host|spmd — "
                         f"'fused' is a loop-level mode, not a "
                         f"single-round one)")
    return relax_spmd_directed(g, values, labels, frontier, cfg, op,
                               collect_stats=collect_stats,
                               return_active=return_active)


_round = relax_round                     # internal alias, kept short


def step_batch(g, labels, frontier, cfg, op, mode="host",
               collect_stats=False):
    """One serving step over ``[B, V]`` slot state: a balancer round
    followed by the min-combine frontier update (a vertex re-enters its
    query's worklist exactly when its label improved).  Returns
    ``(labels, next_frontier, RoundStats|None)``.

    This is the continuous-batching engine's inner loop body
    (DESIGN.md section 8): rows are independent, so the caller may
    retire/refill any subset of rows between steps — at fixed shapes,
    hence without recompiling — and every row still evolves bitwise
    like its standalone single-source run.  Only ``min``-combine
    operators (the point-query apps in :data:`QUERY_APPS`) are valid
    here."""
    if op.combine != "min":
        raise ValueError(f"step_batch serves min-combine point queries; "
                         f"got {op.name} (combine={op.combine!r})")
    old = labels
    labels, st = relax_round(g, labels, labels, frontier, cfg, op,
                             collect_stats=collect_stats, mode=mode)
    return labels, labels < old, st


# the point-query applications a serving deployment admits: name ->
# (operator, label fill value).  Initial state for a fresh query is
# multi_source_state / frontier.refill_rows with that fill.
QUERY_APPS = {
    "bfs": (ops.BFS_HOP, INF),
    "sssp": (ops.SSSP_RELAX, INF),
}


def resume_loop(g, labels, frontier, cfg, op, max_rounds: int = 10_000,
                collect_stats: bool = False, mode: str = "host",
                direction: Optional[str] = None) -> "AppResult":
    """Continue a min-combine data-driven loop from explicit
    labels/frontier state until the worklist drains.

    This is the incremental-repair entry point of the streaming layer
    (DESIGN.md section 10): ``repro.core.streaming.stream_update``
    seeds ``frontier`` from the endpoints of changed edges and resumes
    the ordinary round loop over the current labels — the exact loop
    :func:`bfs`/:func:`sssp`/:func:`cc` run, so every strategy,
    backend, execution mode, and traversal direction applies to repair
    rounds unchanged.  Only ``min``-combine operators are monotone
    under resumption (labels can only improve), so others are
    rejected."""
    if op.combine != "min":
        raise ValueError(f"resume_loop repairs min-combine fixpoints; "
                         f"got {op.name} (combine={op.combine!r})")
    cfg = _with_direction(cfg, direction)
    labels, rounds, secs, stats, syncs = _loop(
        g, lambda l: l, labels, frontier, cfg, op, max_rounds,
        collect_stats, next_frontier=lambda old, new, f: new < old,
        mode=mode)
    return AppResult(labels, rounds, secs, stats, syncs)


def _loop(g: Graph, values_of, labels, frontier, cfg, op,
          max_rounds: int, collect_stats: bool,
          next_frontier, post_round=None, mode: str = "host"):
    """Generic data-driven loop with explicit current/next worklists.

    In host/spmd mode, convergence is driven by the round's own
    ``return_active`` liveness (in host mode a slice of the fused count
    transfer the round already pays for) rather than a separate
    blocking ``jnp.any(frontier)``, so a host-mode round costs exactly
    ONE device->host transfer; an empty frontier is detected by the
    same probe, before any work launches.  ``mode="fused"`` hands the
    whole loop to :func:`repro.core.balancer.run_fused` instead — one
    ``lax.while_loop``, no per-round transfers at all.

    Returns ``(labels, rounds, seconds, stats, host_transfers)``;
    ``host_transfers`` is measured as the delta of the balancer's sync
    counter across the loop, so it is 0 for fused mode by construction
    *and* by observation.
    """
    t_sync = host_transfer_count()
    if mode == "fused":
        # fused mode fuses the min-combine `new < old` frontier update;
        # loops needing a post_round hook keep their own fused variant
        assert post_round is None
        t0 = time.perf_counter()
        labels, _, r, st_dev = run_fused(g, labels, frontier, cfg, op,
                                         max_rounds, collect_stats)
        jax.block_until_ready(labels)
        secs = time.perf_counter() - t0
        stats = fused_stats_host(st_dev, int(r)) if collect_stats else None
        return (labels, int(r), secs, stats,
                host_transfer_count() - t_sync)
    stats = [] if collect_stats else None
    t0 = time.perf_counter()
    rounds = 0
    while rounds < max_rounds:
        # the round's host spans (``graph.round`` around ``graph.counts``
        # / ``graph.plan`` / ``graph.passes`` inside ``relax``, and
        # ``graph.update``) put each device idle gap of a profiler trace
        # down to a phase of the round; the last span is the probe that
        # finds the frontier empty
        with jax.profiler.TraceAnnotation("graph.round", round=rounds):
            old = labels
            new, st, active = _round(g, values_of(labels), labels,
                                     frontier, cfg, op, collect_stats, mode,
                                     return_active=True)
            if not bool(np.any(active)):
                break                  # frontier empty: converged
            with jax.profiler.TraceAnnotation("graph.update"):
                labels = new
                if post_round is not None:
                    labels = post_round(labels)
                frontier = next_frontier(old, labels, frontier)
        if collect_stats and st is not None:
            stats.append(st)
        rounds += 1
    jax.block_until_ready(labels)
    return (labels, rounds, time.perf_counter() - t0, stats,
            host_transfer_count() - t_sync)


# ---------------------------------------------------------------------------

def _with_direction(cfg: BalancerConfig, direction) -> BalancerConfig:
    """Per-call ``direction=`` override of the strategy config
    (``push`` | ``pull`` | ``adaptive`` — DESIGN.md section 9); None
    keeps ``cfg.direction``.  The replaced config hashes by value, so
    overriding costs no extra jit traces."""
    if direction is None:
        return cfg
    return dataclasses.replace(cfg, direction=direction)


def sssp(g: Graph, source: int, cfg: BalancerConfig = BalancerConfig(),
         max_rounds: int = 10_000, collect_stats: bool = False,
         mode: str = "host", direction: Optional[str] = None) -> AppResult:
    """Bellman-Ford style data-driven SSSP (min-combine relaxation;
    ``direction`` selects push/pull/adaptive rounds per DESIGN.md
    section 9)."""
    cfg = _with_direction(cfg, direction)
    dist = jnp.full((g.num_vertices,), INF, dtype=jnp.int32).at[source].set(0)
    frontier = single_source(g.num_vertices, source)
    labels, rounds, secs, stats, syncs = _loop(
        g, lambda l: l, dist, frontier, cfg, ops.SSSP_RELAX, max_rounds,
        collect_stats, next_frontier=lambda old, new, f: new < old,
        mode=mode)
    return AppResult(labels, rounds, secs, stats, syncs)


def bfs(g: Graph, source: int, cfg: BalancerConfig = BalancerConfig(),
        max_rounds: int = 10_000, collect_stats: bool = False,
        mode: str = "host", direction: Optional[str] = None) -> AppResult:
    """Data-driven BFS: hop-count labels via min-combine rounds
    (``direction`` selects push/pull/adaptive per DESIGN.md
    section 9)."""
    cfg = _with_direction(cfg, direction)
    level = jnp.full((g.num_vertices,), INF, dtype=jnp.int32).at[source].set(0)
    frontier = single_source(g.num_vertices, source)
    labels, rounds, secs, stats, syncs = _loop(
        g, lambda l: l, level, frontier, cfg, ops.BFS_HOP, max_rounds,
        collect_stats, next_frontier=lambda old, new, f: new < old,
        mode=mode)
    return AppResult(labels, rounds, secs, stats, syncs)


# ---- batched multi-source queries (DESIGN.md section 7) -------------------

def _batch_loop(g: Graph, labels, frontier, cfg, op, max_rounds,
                collect_stats, mode) -> AppResult:
    """The shared multi-query convergence loop: identical round
    structure to :func:`_loop`, but over ``[B, V]`` state — each round
    is ONE balancer invocation serving the whole batch, and queries
    whose frontier row has emptied are retired implicitly (they no
    longer contribute to the union the round plans over)."""
    labels, rounds, secs, stats, syncs = _loop(
        g, lambda l: l, labels, frontier, cfg, op, max_rounds,
        collect_stats, next_frontier=lambda old, new, f: new < old,
        mode=mode)
    return AppResult(labels, rounds, secs, stats, syncs)


def sssp_batch(g: Graph, sources, cfg: BalancerConfig = BalancerConfig(),
               max_rounds: int = 10_000, collect_stats: bool = False,
               mode: str = "host",
               direction: Optional[str] = None) -> AppResult:
    """Batched multi-source SSSP: ``labels[b]`` equals (bitwise) the
    single-source :func:`sssp` labels for ``sources[b]``, computed by
    one union-frontier round loop for all B sources.  ``direction``
    selects push/pull/adaptive rounds (DESIGN.md section 9); the
    adaptive choice is made on the union frontier for the whole
    batch."""
    cfg = _with_direction(cfg, direction)
    labels, frontier = multi_source_state(g.num_vertices, sources, INF)
    return _batch_loop(g, labels, frontier, cfg, ops.SSSP_RELAX,
                       max_rounds, collect_stats, mode)


def bfs_batch(g: Graph, sources, cfg: BalancerConfig = BalancerConfig(),
              max_rounds: int = 10_000, collect_stats: bool = False,
              mode: str = "host",
              direction: Optional[str] = None) -> AppResult:
    """Batched multi-source BFS (see :func:`sssp_batch`)."""
    cfg = _with_direction(cfg, direction)
    labels, frontier = multi_source_state(g.num_vertices, sources, INF)
    return _batch_loop(g, labels, frontier, cfg, ops.BFS_HOP,
                       max_rounds, collect_stats, mode)


def cc(g: Graph, cfg: BalancerConfig = BalancerConfig(),
       max_rounds: int = 10_000, collect_stats: bool = False,
       mode: str = "host", direction: Optional[str] = None) -> AppResult:
    """Connected components by min-label propagation.

    Computes weakly-connected components when ``g`` is symmetrized
    (the benchmark harness symmetrizes, matching standard practice).
    ``direction`` selects push/pull/adaptive rounds (DESIGN.md
    section 9) — on the dense early frontiers of cc, adaptive rounds
    run as pulls.
    """
    cfg = _with_direction(cfg, direction)
    comp = jnp.arange(g.num_vertices, dtype=jnp.int32)
    frontier = full_frontier(g.num_vertices)
    labels, rounds, secs, stats, syncs = _loop(
        g, lambda l: l, comp, frontier, cfg, ops.CC_MIN, max_rounds,
        collect_stats, next_frontier=lambda old, new, f: new < old,
        mode=mode)
    return AppResult(labels, rounds, secs, stats, syncs)


@partial(jax.jit, static_argnames=("k", "cfg", "max_rounds",
                                   "collect_stats"))
def _kcore_fused(g: Graph, deg, frontier, dead_acc, k: int,
                 cfg: BalancerConfig, max_rounds: int,
                 collect_stats: bool):
    """kcore's whole peeling loop as ONE ``lax.while_loop`` (zero
    per-round host syncs): the balancer round is the device-resident
    :func:`repro.core.balancer.relax_fused_round`, and the
    newly-dead bookkeeping — the host loop's ``post_round`` logic —
    moves into the loop body unchanged."""
    st0 = (_fused_stats_init(max_rounds, 1)
           if collect_stats else None)

    def cond(carry):
        r, deg, dead, fr, st = carry
        return (r < max_rounds) & jnp.any(fr)

    def body(carry):
        r, deg, dead, fr, st = carry
        new_deg, _, _, _, row = relax_fused_round(
            g, None, None, deg[None], deg[None], fr[None], cfg,
            ops.KCORE_DEC, None, collect_stats)
        new_deg = new_deg[0]
        newly_dead = (new_deg < k) & ~dead
        if collect_stats:
            st = jax.tree_util.tree_map(
                lambda buf, x: buf.at[r].set(x), st, row)
        return r + 1, new_deg, dead | newly_dead, newly_dead, st

    r, deg, dead, fr, st = jax.lax.while_loop(
        cond, body, (jnp.int32(0), deg, dead_acc, frontier, st0))
    return (~dead).astype(jnp.int32), r, st


def kcore(g: Graph, k: int, cfg: BalancerConfig = BalancerConfig(),
          max_rounds: int = 10_000, collect_stats: bool = False,
          mode: str = "host") -> AppResult:
    """k-core decomposition: labels[v] = 1 if v is in the k-core.

    Push formulation: when a vertex dies its neighbours lose one degree
    (the paper uses the pull variant; the fixpoint is identical).
    Expects a symmetrized graph.
    """
    deg = g.out_degrees().astype(jnp.int32)
    alive = deg >= k
    frontier = ~alive & (deg > 0)          # initially-dead vertices push
    dead_acc = frontier | ~alive
    if mode == "fused":
        # validate direction x operator exactly like the per-round modes
        if cfg.direction != "push":
            ops.as_pull(ops.KCORE_DEC)     # raises: add-combine op
        t_sync = host_transfer_count()
        t0 = time.perf_counter()
        in_core, r, st_dev = _kcore_fused(g, deg, frontier, dead_acc,
                                          int(k), cfg, max_rounds,
                                          collect_stats)
        jax.block_until_ready(in_core)
        secs = time.perf_counter() - t0
        stats = fused_stats_host(st_dev, int(r)) if collect_stats else None
        return AppResult(in_core, int(r), secs, stats,
                         host_transfer_count() - t_sync)
    stats = [] if collect_stats else None
    t_sync = host_transfer_count()
    t0 = time.perf_counter()
    rounds = 0
    while rounds < max_rounds:
        new_deg, st, active = _round(g, deg, deg, frontier, cfg,
                                     ops.KCORE_DEC, collect_stats, mode,
                                     return_active=True)
        if not bool(np.any(active)):
            break                      # no vertex died last round
        deg = new_deg
        newly_dead = (deg < k) & ~dead_acc
        dead_acc = dead_acc | newly_dead
        frontier = newly_dead
        if collect_stats and st is not None:
            stats.append(st)
        rounds += 1
    jax.block_until_ready(deg)
    in_core = (~dead_acc).astype(jnp.int32)
    return AppResult(in_core, rounds, time.perf_counter() - t0, stats,
                     host_transfer_count() - t_sync)


@partial(jax.jit, static_argnames=("damping",))
def _pr_round_math(rank, inv_out, sink, acc, damping: float):
    """The scalar arithmetic around PageRank's relax round, shared by
    the host loop and the fused while_loop so both take the SAME fusion
    decisions (an enclosing jit would otherwise contract the update
    into an FMA and perturb the last f32 bit).  Called with ``acc=None``
    for the pre-round pieces, with the scattered ``acc`` for the
    post-round update + residual."""
    n = rank.shape[0]
    if acc is None:
        contrib = rank * inv_out
        dangling = jnp.sum(jnp.where(sink, rank, 0.0))
        return contrib, dangling
    dangling = jnp.sum(jnp.where(sink, rank, 0.0))
    new_rank = (1.0 - damping) / n + damping * (acc + dangling / n)
    delta = jnp.max(jnp.abs(new_rank - rank))
    return new_rank, delta


@partial(jax.jit, static_argnames=("damping", "tol", "cfg",
                                   "max_rounds", "collect_stats"))
def _pagerank_fused(rg: Graph, inv_out, sink, damping: float,
                    tol: float, cfg: BalancerConfig, max_rounds: int,
                    collect_stats: bool):
    """PageRank's whole power iteration as ONE ``lax.while_loop``:
    the residual check that used to block the host every round
    (``float(jnp.max(...))``) becomes part of the loop condition on
    device.  The per-round arithmetic goes through ``_pr_round_math``
    — the same jitted subgraph the host loop calls — so f32 rounding
    is bitwise-identical between the two modes."""
    n = inv_out.shape[0]
    rank0 = jnp.full((n,), 1.0 / n, dtype=jnp.float32)
    frontier = full_frontier(n)
    st0 = (_fused_stats_init(max_rounds, 1)
           if collect_stats else None)

    def cond(carry):
        r, rank, delta, st = carry
        return (r < max_rounds) & (delta >= tol)

    def body(carry):
        r, rank, delta, st = carry
        contrib, _ = _pr_round_math(rank, inv_out, sink, None, damping)
        acc = jnp.zeros((n,), jnp.float32)
        # pull: gather contrib at in-neighbours, scatter-add at anchor
        acc, _, _, _, row = relax_fused_round(
            rg, None, None, contrib[None], acc[None], frontier[None],
            cfg, ops.PR_PULL, None, collect_stats)
        acc = acc[0]
        new_rank, delta = _pr_round_math(rank, inv_out, sink, acc,
                                         damping)
        if collect_stats:
            st = jax.tree_util.tree_map(
                lambda buf, x: buf.at[r].set(x), st, row)
        return r + 1, new_rank, delta, st

    r, rank, _, st = jax.lax.while_loop(
        cond, body, (jnp.int32(0), rank0, jnp.float32(jnp.inf), st0))
    return rank, r, st


def pagerank(g: Graph, damping: float = 0.85, tol: float = 1e-6,
             cfg: BalancerConfig = BalancerConfig(),
             max_rounds: int = 1000, collect_stats: bool = False,
             rg: Graph | None = None, mode: str = "host") -> AppResult:
    """Pull-style topology-driven PageRank (residual tolerance).

    Dangling vertices (out-degree 0) redistribute their rank mass
    uniformly each round, so ``sum(rank) == 1`` is preserved on graphs
    with sinks — without this, sinks leak mass every round, ranks
    deflate, and ``tol`` is checked against shrunken values."""
    n = g.num_vertices
    if rg is None:
        rg = g.reverse()                   # pull traverses in-edges
    outdeg = g.out_degrees().astype(jnp.float32)
    inv_out = jnp.where(outdeg > 0, 1.0 / jnp.maximum(outdeg, 1.0), 0.0)
    sink = outdeg == 0
    if mode == "fused":
        if cfg.direction != "push":
            ops.as_pull(ops.PR_PULL)       # raises: not a push-min op
        t_sync = host_transfer_count()
        t0 = time.perf_counter()
        rank, r, st_dev = _pagerank_fused(rg, inv_out, sink,
                                          float(damping), float(tol),
                                          cfg, max_rounds,
                                          collect_stats)
        jax.block_until_ready(rank)
        secs = time.perf_counter() - t0
        stats = fused_stats_host(st_dev, int(r)) if collect_stats else None
        return AppResult(rank, int(r), secs, stats,
                         host_transfer_count() - t_sync)
    rank = jnp.full((n,), 1.0 / n, dtype=jnp.float32)
    frontier = full_frontier(n)
    stats = [] if collect_stats else None
    t_sync = host_transfer_count()
    t0 = time.perf_counter()
    rounds = 0
    while rounds < max_rounds:
        contrib, _ = _pr_round_math(rank, inv_out, sink, None,
                                    float(damping))
        acc = jnp.zeros((n,), jnp.float32)
        # pull: gather contrib at in-neighbours, scatter-add at anchor
        acc, st = _round(rg, contrib, acc, frontier, cfg, ops.PR_PULL,
                         collect_stats, mode)
        new_rank, delta_dev = _pr_round_math(rank, inv_out, sink, acc,
                                             float(damping))
        delta = float(delta_dev)
        _note_host_transfer()          # the residual check blocks
        rank = new_rank
        if collect_stats and st is not None:
            stats.append(st)
        rounds += 1
        if delta < tol:
            break
    jax.block_until_ready(rank)
    return AppResult(rank, rounds, time.perf_counter() - t0, stats,
                     host_transfer_count() - t_sync)
