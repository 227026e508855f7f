"""Adaptive Load Balancer (ALB) — the paper's core contribution, on TPU.

Four strategies (Section 3 + 4 of the paper):

* ``vertex``  — vertex-based distribution: every active vertex processed
  as one unit of work regardless of degree (Section 3.1 strawman).
* ``twc``     — Thread-Warp-CTA analog: active vertices binned by degree
  (small/medium/large); each bin processed with a uniform inner width.
  The large bin is UNBOUNDED, which is exactly the thread-block
  imbalance the paper fixes (Section 3.2).
* ``edge_lb`` — non-adaptive edge-balanced distribution (Gunrock-LB
  analog): ALL frontier edges are renumbered by prefix sum and dealt
  evenly (Section 3.3).
* ``alb``     — the paper's scheme: TWC bins for degree < THRESHOLD plus
  a ``huge`` bin; an inspector checks whether the huge bin is nonempty
  and only then runs the edge-balanced (LB) executor (Section 4).

TPU mapping (DESIGN.md section 2): GPU thread blocks -> Pallas grid
tiles; warps/threads -> VPU lanes; atomicMin -> XLA scatter-min;
the inspector -> a vector reduction + host/`lax.cond` dispatch; cyclic
vs blocked edge deal -> lane-major contiguous vs strided edge-id order.

Architecture (DESIGN.md section 3): a strategy is *planned* —
``make_plan`` turns a :class:`BalancerConfig` into a :class:`RoundPlan`
of degree bins plus an LB mode for the static-capacity rounds, and
``host_plan`` into the host round's, which for ``alb`` splits the bins
below the threshold into a finer ladder — and *executed* by one of two
interchangeable executor pairs from the registry:

* ``xla``    — pure jnp building blocks (``_bin_pass`` / ``_lb_pass``),
* ``pallas`` — the mapping kernels in ``repro.kernels`` (selected by
  ``BalancerConfig.use_pallas``), registered lazily.

Each :class:`ExecutorPair` exposes every path twice:

* host entries (``bin_host`` / ``lb_host``): per-round host decisions +
  bucketed jit shapes — mirrors per-round GPU kernel launches; used by
  ``relax`` for the single-device wall-clock benchmarks.
* fully-jit entries (``bin_jit`` / ``lb_jit``): static capacities,
  traced chunk index, ``lax.cond`` inspector — used by ``relax_spmd``
  inside ``shard_map`` for the distributed (Gluon-analog) runtime.

Both rounds therefore run the *same* executor implementations;
``use_pallas=True`` routes the hot mapping loops through the Pallas
kernels in either mode.

Batched multi-source queries (DESIGN.md section 7): ``relax`` and
``relax_spmd`` also accept ``labels[B, V]`` / ``values[B, V]`` /
``frontier[B, V]`` — B independent queries over the shared CSR.  Bin
selection, the huge-bin inspector, and the LB prefix-sum deal all run
once over the **union** frontier; per-query activity is recovered by
gathering the ``[B, V]`` frontier mask at each enumerated edge's
anchor vertex, and candidates of inactive (vertex, query) pairs carry
the combiner's identity so skipping them is exact.  One kernel launch
therefore serves B queries instead of B launches serving one.

Traversal direction (DESIGN.md section 9): the same fused host counts
that drive the strategy's inspector also drive a Beamer-style
*direction* choice — ``BalancerConfig.direction`` is ``push`` (as the
operator is written), ``pull`` (the operator's pull twin over the
cached reverse CSR: gather value and activity at each in-edge's
source, combine at the anchor), or ``adaptive``
(:func:`resolve_direction` per round, no extra device sync).  Pull
enumeration is frontier-independent — every vertex with in-edges,
binned by in-degree — so it is planned once per graph and cached
(:func:`_pull_enum`).  For push min-combine operators the pull round
is bitwise equal to the push round.

The continuous-batching service (DESIGN.md section 8) leans on one
further property of the batched round: rows are *independent*.  A row
whose frontier is empty contributes no live candidates anywhere, so
its labels are frozen — which is what lets the serving engine retire a
converged query's slot and refill it mid-loop.  ``relax``'s
``return_active`` surfaces each row's entered-the-round liveness from
the fused host transfer the round already pays for (free
instrumentation for external loops; retirement itself is a post-round
fact the engine reads from the updated frontier).
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Graph
from .frontier import (next_bucket, compact, count, dirty_mask,
                       prefix_sum, scatter_rows, spread_index,
                       union_frontier)
from .operators import Operator, as_pull


@dataclasses.dataclass(frozen=True)
class BalancerConfig:
    """Everything that defines a load-balancing strategy instance; a
    frozen (hashable) value object, so it doubles as a jit static arg
    and as the ``strategy`` component of the serving-layer result-cache
    key (DESIGN.md section 8)."""
    strategy: str = "alb"            # vertex | twc | edge_lb | alb
    threshold: int = 1024            # paper: #threads launched
    small_width: int = 8             # thread-level bin
    medium_width: int = 128          # warp-level bin
    large_width: int = 1024          # CTA chunk width (per pass)
    distribution: str = "cyclic"     # cyclic | blocked (Section 4.1)
    num_tiles: int = 64              # "thread blocks": LB deal/kernels
    use_pallas: bool = False         # route hot loops through Pallas
    lb_tile_edges: int = 2048        # edge tile per grid step (LB kernel)
    direction: str = "push"          # push | pull | adaptive (sec. 9)
    pull_alpha: int = 14             # adaptive: pull when m_f*alpha >= E
    pull_beta: int = 24              # adaptive: pull when n_f*beta >= V
    backend: Optional[str] = None    # xla | pallas | merge_path | None
    #                                  (None: derived from use_pallas)
    wire: str = "identity"           # sync wire codec: identity |
    #                                  delta | quantize[:<dtype>] |
    #                                  bitmap (DESIGN.md section 14)

    def __post_init__(self):
        assert self.strategy in ("vertex", "twc", "edge_lb", "alb")
        assert self.distribution in ("cyclic", "blocked")
        assert self.direction in ("push", "pull", "adaptive")
        assert self.backend in (None, "xla", "pallas", "merge_path")
        # syntax-level wire validation; the operator pairing (quantize
        # needs a declared safe narrowing) is checked at driver entry,
        # where the operator is known (repro.core.wire.get_codec)
        from .wire import validate_wire   # local: avoids import cycle
        validate_wire(self.wire)

    @property
    def executor(self) -> str:
        """Registry name of the backend this config routes through.

        An explicit ``backend`` wins; otherwise ``use_pallas`` selects
        between the classic ``xla`` and ``pallas`` pairs.  The third
        registered backend, ``merge_path``, replaces the whole
        plan/inspector machinery with equal-work edge tiles (see
        :func:`effective_plan`)."""
        if self.backend is not None:
            return self.backend
        return "pallas" if self.use_pallas else "xla"


# ---------------------------------------------------------------------------
# round planner — the ONE place a strategy is defined: make_plan's bins
# for the static-capacity rounds, host_plan's for the host-driven round
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BinSpec:
    """One degree bin of the vertex-binned (TWC-analog) path.

    Both plans of a strategy are made of them: :func:`make_plan`'s (the
    static-capacity rounds) and :func:`host_plan`'s (the host-driven
    round).

    A frontier vertex lands in the bin when ``lo < deg`` and (if ``hi``
    is set) ``deg <= hi``.  ``cap`` is a static upper bound on the
    degree of any member (used by the fully-jit round to fix the pass
    count); ``cap=None`` marks a genuinely unbounded bin, driven by a
    data-dependent number of width-``width`` passes.  A pass over the
    bin issues ``width`` slots per member, used or not.
    """
    name: str
    width: int
    lo: int
    hi: Optional[int] = None
    cap: Optional[int] = None

    def mask(self, deg: jax.Array, valid: jax.Array) -> jax.Array:
        """Membership mask of this bin over a frontier's degrees."""
        m = valid & (deg > self.lo)
        if self.hi is not None:
            m = m & (deg <= self.hi)
        return m

    def static_passes(self) -> Optional[int]:
        """Pass count for the fully-jit round; None = data-dependent."""
        if self.cap is None:
            return None
        return max(1, -(-self.cap // self.width))


@dataclasses.dataclass(frozen=True)
class RoundPlan:
    """Bins + LB mode for one strategy.

    ``lb``: ``"none"`` (no edge-balanced path), ``"all"`` (every
    frontier edge goes through LB — the non-adaptive Gunrock analog) or
    ``"huge"`` (only vertices with ``deg >= threshold`` — the paper's
    inspector-guarded adaptive path).

    ``direction``: the traversal-direction policy of the strategy
    instance (``push`` | ``pull`` | ``adaptive`` — DESIGN.md
    section 9); ``adaptive`` is resolved per round by
    :func:`resolve_direction` from the fused host counts.
    """
    bins: tuple
    lb: str
    direction: str = "push"

    def lb_mask(self, deg, valid, threshold: int):
        """Which frontier vertices the edge-balanced path serves."""
        if self.lb == "all":
            return valid & (deg > 0)
        if self.lb == "huge":
            return valid & (deg >= threshold)
        raise ValueError(self.lb)


def make_plan(cfg: BalancerConfig) -> RoundPlan:
    """Turn a config into the degree bins + LB mode of its strategy —
    the paper's bins, which the static-capacity rounds run
    (``relax_spmd``, ``run_fused``, the Gluon rounds and the serving
    engine's fused loop).

    Those rounds run every bin at capacity V, so each bin costs a
    ``[V, width]`` pass whatever its membership, and few wide bins are
    cheapest there: ``alb``'s three (small/medium/large, widths
    8/128/1024 by default) sum to a width of 1,160 per round.  The
    host-driven round gathers each bin at a capacity sized to its
    members and runs :func:`host_plan`'s finer ladder instead
    (DESIGN.md section 3)."""
    s, sw, mw, lw, th = (cfg.strategy, cfg.small_width, cfg.medium_width,
                         cfg.large_width, cfg.threshold)
    d = cfg.direction
    if s == "vertex":
        # one unit of work per vertex, inner width = whole adjacency
        return RoundPlan((BinSpec("vertex", lw, 0),), "none", d)
    if s == "twc":
        return RoundPlan((BinSpec("small", sw, 0, sw, sw),
                          BinSpec("medium", mw, sw, mw, mw),
                          # CTA bin: UNBOUNDED — the paper's culprit
                          BinSpec("large", lw, mw)), "none", d)
    if s == "edge_lb":
        return RoundPlan((), "all", d)        # everything, non-adaptive
    # alb: bins must be DISJOINT with the huge bin or add-combine
    # operators double-count (min-combine would mask the bug)
    return RoundPlan((BinSpec("small", sw, 0, min(sw, th - 1), sw),
                      BinSpec("medium", mw, sw, min(mw, th - 1), mw),
                      BinSpec("large", lw, mw, th - 1, th)), "huge", d)


def effective_plan(cfg: BalancerConfig) -> RoundPlan:
    """The plan a static-capacity round actually executes.

    Normally :func:`make_plan`'s strategy bins; under the
    ``merge_path`` backend the plan collapses to ``RoundPlan((),
    "all")`` regardless of strategy — merge-path partitions the
    frontier's whole edge range into equal-work tiles by co-ranked
    binary search over the CSR prefix sums, so it needs no degree bins
    and no huge-bin inspector.  Every frontier edge is still processed
    exactly once (the LB mask covers all ``deg > 0`` members), so
    add-combine operators stay exact."""
    if cfg.executor == "merge_path":
        return RoundPlan((), "all", cfg.direction)
    return make_plan(cfg)


def _ladder_bins(small_width: int, threshold: int) -> tuple:
    """``alb``'s bins below ``threshold`` as a doubling ladder: (0,
    small_width] at width ``small_width``, then (w/2, w] at width w,
    doubling w until a bin reaches ``threshold - 1``, where the last
    one ends.  Every bin takes one pass (``cap = width``), above the
    first rung a member fills more than half of its row, and the bins
    stay disjoint with each other and with the huge bin (``deg >=
    threshold``)."""
    top = threshold - 1
    hi = min(small_width, top)
    bins = [BinSpec(f"w{small_width}", small_width, 0, hi, small_width)]
    width = small_width
    while hi < top:
        lo, width = width, 2 * width
        hi = min(width, top)
        bins.append(BinSpec(f"w{width}", width, lo, hi, width))
    return tuple(bins)


def host_plan(cfg: BalancerConfig) -> RoundPlan:
    """The plan the host-driven round (:func:`relax`) executes.

    :func:`effective_plan`'s, apart from ``alb``: there the host round
    runs the degree ladder of :func:`_ladder_bins` (by default widths
    8, 16, ..., 1024) in place of the paper's three bins.  The host
    round gathers each bin at the power-of-two bucket above its member
    count, so a bin's slots are its members times its width: above the
    first rung a member fills more than half of its row, where a
    degree-129 member of the paper's large bin (width 1024) fills 13%.
    The static-capacity rounds, which pay ``[V, width]`` per bin, keep
    :func:`make_plan`'s bins.  The huge bin and the LB pass are the
    same in both plans."""
    plan = effective_plan(cfg)
    if plan.lb != "huge":          # not alb, or merge_path's LB-all plan
        return plan
    return RoundPlan(_ladder_bins(cfg.small_width, cfg.threshold),
                     plan.lb, plan.direction)


def resolve_direction(cfg: BalancerConfig, frontier_size: int,
                      frontier_edges: int, num_vertices: int,
                      num_edges: int) -> str:
    """Per-round traversal-direction choice (DESIGN.md section 9).

    ``push`` / ``pull`` configs are fixed; ``adaptive`` applies
    Beamer-style direction-optimization thresholds to the union
    frontier: the round runs as a pull (gather over in-edges of the
    cached reverse CSR) when the frontier is dense by vertices
    (``frontier_size * pull_beta >= V``) or by out-edges
    (``frontier_edges * pull_alpha >= E``), and as a push otherwise.
    Both inputs ride the fused host-count transfer every round already
    pays (``_host_round_counts``), so adaptivity adds no device sync.
    """
    if cfg.direction != "adaptive":
        return cfg.direction
    if frontier_size * cfg.pull_beta >= num_vertices:
        return "pull"
    if frontier_edges * cfg.pull_alpha >= num_edges:
        return "pull"
    return "push"


def resolve_direction_device(cfg: BalancerConfig, frontier_size,
                             frontier_edges, num_vertices: int,
                             num_edges: int) -> jax.Array:
    """jit-traceable twin of :func:`resolve_direction`: the same Beamer
    thresholds over *device* int32 scalars, returning a bool scalar
    (True = pull) instead of a string — the branch selector the fused
    round feeds to ``lax.cond``.  Fixed directions fold to constants at
    trace time; the integer threshold arithmetic is exact, so the
    device choice is always identical to the host choice made from the
    fused count transfer.  (Counts are int32 on device — frontier sizes
    or edge totals beyond ``2**31 / max(alpha, beta)`` would need the
    x64 mode this repo does not enable.)"""
    if cfg.direction == "push":
        return jnp.asarray(False)
    if cfg.direction == "pull":
        return jnp.asarray(True)
    return ((frontier_size * cfg.pull_beta >= num_vertices)
            | (frontier_edges * cfg.pull_alpha >= num_edges))


# ---------------------------------------------------------------------------
# process-wide counters: named, monotonic host integers, bumped from
# values a round already holds on the host (never a device read), so
# that they are always on.  ``host_transfers`` counts the per-round
# blocking device->host transfers each execution mode performs, as an
# assertable number (the structural realization of the "zero per-round
# host syncs" property of the fused mode — no wall-clock measurement
# involved); the slot counters count the work the host round's edge
# passes issue against the frontier edges they carry
# ---------------------------------------------------------------------------

_COUNTERS = {
    "host_transfers": 0,
    # bin passes: slots issued (bin capacity x width, per chunk pass)
    # and the bins' frontier edges
    "bin_slots": 0,
    "bin_edges": 0,
    # LB pass: edge ids enumerated (:func:`_lb_enum_size`) and the huge
    # bin's frontier edges
    "lb_slots": 0,
    "lb_edges": 0,
}


def counter_snapshot() -> dict:
    """Every process-wide counter's value now; a traversal's counts are
    the differences of two snapshots around it."""
    return dict(_COUNTERS)


def _note_host_transfer(n: int = 1) -> None:
    """Record ``n`` blocking per-round device->host sync points.

    Called at every site that materializes device values on the host
    *inside* a round loop (the fused count vector of :func:`relax`, the
    liveness/stat fetch of :func:`relax_spmd_directed`, the per-round
    probes of the distributed and serving loops).  One-time amortized
    setup (e.g. the cached pull enumeration) and the final label fetch
    are deliberately NOT counted — ``host_transfers`` measures the
    per-round round-trip cost the fused mode eliminates."""
    _COUNTERS["host_transfers"] += n


def host_transfer_count() -> int:
    """Monotonic process-wide count of per-round device->host sync
    points (see :func:`_note_host_transfer`).  Callers measure a
    traversal's syncs as the delta across it; ``mode="fused"`` must
    leave the counter unchanged between dispatch and final fetch."""
    return _COUNTERS["host_transfers"]


# ---------------------------------------------------------------------------
# executor registry: XLA and Pallas implementations of the two paths,
# each with a host-driven and a fully-jit entry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExecutorPair:
    """One backend's implementations of the bin + LB paths.

    Every entry is **batched**: ``values`` / ``labels`` are ``[B, V]``
    and ``fmask`` is the ``[B, V]`` per-query frontier (the batch axis
    is always present; the round entry points add it for un-batched
    callers).  The vertex/edge enumeration arguments are batch-shared —
    they come from the union frontier — and per-query activity is
    recovered inside the entry by gathering ``fmask`` at each edge's
    anchor vertex.

    bin entries: (g, values, labels, fmask, bvidx, bdeg, brow, width,
                  op, chunk) -> labels, ``chunk`` a Python int (host)
                  or a traced int32 scalar (jit).
    lb entries:  (g, values, labels, fmask, hvidx, hdeg, hrow, total,
                  ecap, op, distribution, num_tiles, tile_edges)
                  -> labels.
    """
    name: str
    bin_host: Callable
    bin_jit: Callable
    lb_host: Callable
    lb_jit: Callable


_REGISTRY: dict = {}


def register_executor(pair: ExecutorPair) -> None:
    """Install (or replace) a named backend in the executor registry."""
    _REGISTRY[pair.name] = pair


def get_executor(name: str) -> ExecutorPair:
    """Look up a backend by name (``"xla"`` | ``"pallas"`` |
    ``"merge_path"``); the Pallas-backed pairs are registered lazily on
    first use to keep their import cost off the common path.

    ``merge_path`` routes every frontier edge through the co-ranked
    equal-work kernel (``kernels/merge_path.py``) — its plan has no
    bins (see :func:`effective_plan`), so its bin entries are
    unreachable and raise if ever called."""
    if name not in _REGISTRY and name in ("pallas", "merge_path"):
        from repro.kernels import ops as kops   # lazy: pallas import cost
        register_executor(ExecutorPair(
            "pallas",
            bin_host=kops.twc_bin_apply, bin_jit=kops.twc_bin_apply_static,
            lb_host=kops.edge_lb_apply, lb_jit=kops.edge_lb_apply_static))
        register_executor(ExecutorPair(
            "merge_path",
            bin_host=kops.merge_path_no_bins,
            bin_jit=kops.merge_path_no_bins,
            lb_host=kops.merge_path_apply,
            lb_jit=kops.merge_path_apply_static))
    return _REGISTRY[name]


class RoundStats(NamedTuple):
    """Instrumentation for Fig 1/5-style plots (host values).

    With a batched round (DESIGN.md section 7) ``frontier_size`` is the
    **union** frontier size (what drives the work done) and
    ``frontier_per_query`` holds the B per-query frontier sizes; the
    edge counts are union counts — each enumerated edge is processed
    once for the whole batch.
    """
    frontier_size: int
    edges_twc: int          # edges processed by the vertex-binned path
    edges_lb: int           # edges processed by the edge-balanced path
    lb_invoked: bool        # did the inspector fire the LB executor?
    mirrors_synced: int = 0  # label entries exchanged by the BSP sync
    bytes_synced: int = 0    # ... as LOGICAL bytes: index word + [B]
    #                          payload per exchanged vertex (0 outside
    #                          the distributed runtime; see gluon.py /
    #                          DESIGN.md section 6)
    bytes_wire: int = 0      # POST-ENCODE bytes of the same exchange
    #                          under cfg.wire (== bytes_synced for the
    #                          identity codec; DESIGN.md section 14)
    frontier_per_query: Optional[np.ndarray] = None  # int64[B]
    direction: str = "push"  # traversal direction this round ran as
    #                          (DESIGN.md section 9)
    frontier_edges: int = 0  # union-frontier out-edge total (the push-
    #                          side m_f the direction choice is made on;
    #                          0 where the round had no host counts)
    host_transfers: int = 0  # blocking device->host sync points this
    #                          round performed (1 for host/spmd rounds,
    #                          0 for rounds inside the fused loop)

    @classmethod
    def from_device(cls, s: "RoundStatsDev") -> "RoundStats":
        """Materialize a jit-safe :class:`RoundStatsDev` on the host."""
        return cls(frontier_size=int(s.frontier_size),
                   edges_twc=int(s.edges_twc),
                   edges_lb=int(s.edges_lb),
                   lb_invoked=bool(s.lb_invoked),
                   mirrors_synced=int(s.mirrors_synced),
                   bytes_synced=int(s.bytes_synced),
                   bytes_wire=int(s.bytes_wire),
                   frontier_per_query=np.asarray(s.frontier_per_query,
                                                 dtype=np.int64),
                   direction="pull" if bool(s.is_pull) else "push",
                   frontier_edges=int(s.frontier_edges))


class RoundStatsDev(NamedTuple):
    """jit-safe RoundStats: every field is a device array, so the
    structure can cross ``jit`` / ``shard_map`` boundaries (the SPMD
    realization of the Fig 1/5 instrumentation).  The fused round loop
    (:func:`run_fused`) accumulates one of these per round into
    ``[max_rounds]``-leading buffers on device and transfers the whole
    structure once at convergence (:func:`fused_stats_host`)."""
    frontier_size: jax.Array     # int32 scalar (union size when batched)
    edges_twc: jax.Array         # int32 scalar
    edges_lb: jax.Array          # int32 scalar
    lb_invoked: jax.Array        # bool scalar
    mirrors_synced: jax.Array    # int32 scalar (filled in by gluon.py)
    bytes_synced: jax.Array      # int32 scalar (filled in by gluon.py)
    bytes_wire: jax.Array = np.int32(0)  # int32 scalar: post-encode
    #                              bytes under cfg.wire (gluon.py)
    frontier_per_query: jax.Array = np.zeros((1,), np.int32)  # int32[B]
    frontier_edges: jax.Array = np.int32(0)   # push-side m_f (union)
    is_pull: jax.Array = np.zeros((), bool)   # direction this round ran


# ---------------------------------------------------------------------------
# XLA building blocks (the "xla" executor; cached per static shape bucket)
# ---------------------------------------------------------------------------

@jax.jit
def _frontier_meta(g: Graph, frontier_idx: jax.Array):
    """degree / row start / validity for a compacted frontier."""
    v = g.row_ptr.shape[0] - 1
    valid = frontier_idx < v
    safe = jnp.where(valid, frontier_idx, 0)
    deg = jnp.where(valid, g.row_ptr[safe + 1] - g.row_ptr[safe], 0)
    row_start = jnp.where(valid, g.row_ptr[safe], 0)
    return deg, row_start, valid


def combine_neutral(combine: str, dtype):
    """Identity element of a combiner: a candidate that can never win a
    ``min`` (dtype max / +inf) or change an ``add`` (0).  Per-query
    masked slots of the batched scatter carry this value so skipping an
    inactive (vertex, query) pair is exact."""
    if combine == "min":
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.asarray(jnp.inf, dtype)
        return jnp.asarray(jnp.iinfo(dtype).max, dtype)
    if combine == "add":
        return jnp.asarray(0, dtype)
    raise ValueError(combine)


def _apply(labels, target, cand, emask, live, combine):
    """Batched scatter-combine (atomicMin/atomicAdd analog).

    labels : [B, V];  target/emask : batch-shared enumeration shape [S]
    (slots with ``emask`` False are dropped via the out-of-range
    sentinel); ``live`` : [B, *S-broadcastable] per-query activity —
    slots live for some queries but not others keep the shared target
    and carry the combiner's identity where inactive.
    """
    v = labels.shape[-1]
    tgt = jnp.where(emask, target, v)          # out of range => dropped
    full = live & emask[None]
    cand = cand.astype(labels.dtype)
    if combine == "min":
        cand = jnp.where(full, cand, combine_neutral("min", labels.dtype))
    elif combine == "add":
        cand = jnp.where(full, cand, 0)
    else:
        raise ValueError(combine)
    return scatter_rows(labels, tgt, cand, combine)


def _bin_pass_impl(g: Graph, values, labels, fmask, vidx, deg, row_start,
                   width: int, op: Operator, chunk):
    """Process one degree bin: each vertex in ``vidx`` contributes its
    edges [chunk*width, chunk*width + width) — the uniform-trip-count
    vertex-tiled path (TWC small/medium/large analog).  ``chunk`` may be
    a Python int or a traced int32 scalar.

    Shapes: values/labels/fmask: [B, V];  vidx/deg/row_start: [N]
    (union-frontier bin members);  produces an [N, width] edge tile
    shared by the whole batch.

    The work sits in three named scopes, which a profiler trace
    carries as op metadata: ``edges`` (slot -> edge ids and the
    ``col_idx``/``edge_w`` gathers), ``sources`` (the ``fmask``/
    ``values`` gathers and ``op.msg``) and ``combine`` (the scatter).
    """
    v = labels.shape[-1]
    with jax.named_scope("edges"):
        base = jnp.asarray(chunk, jnp.int32) * width
        off = base + jnp.arange(width, dtype=jnp.int32)[None, :]  # [1,W]
        emask = off < deg[:, None]                                 # [N,W]
        graph_e = spread_index(emask, row_start[:, None] + off,
                               g.col_idx.shape[0])
        dst = g.col_idx[graph_e]
        w = g.edge_w[graph_e]
    if op.direction == "push":
        with jax.named_scope("sources"):
            vsafe = jnp.where(vidx < v, vidx, 0)
            live = fmask[:, vsafe][:, :, None]                     # [B,N,1]
            val = values[:, vsafe][:, :, None]                     # [B,N,1]
            cand = op.msg(val, w[None])
        target = dst
    else:  # pull: value AND activity gathered at the in-neighbour
        # (``dst`` in the reverse CSR is the original edge's source),
        # candidate scattered at the anchor — DESIGN.md section 9
        with jax.named_scope("sources"):
            live = fmask[:, dst]                                   # [B,N,W]
            val = values[:, dst]                                   # [B,N,W]
            cand = op.msg(val, w[None])
        target = jnp.broadcast_to(vidx[:, None], emask.shape)
    with jax.named_scope("combine"):
        return _apply(labels, target, cand, emask, live, op.combine)


_bin_pass = partial(jax.jit, static_argnames=("width", "op"))(_bin_pass_impl)


def _segment_values(starts, vals, n: int):
    """``vals[j(i)]`` for every ``i`` in ``[0, n)``, ``j(i)`` the last
    ``k`` with ``starts[k] <= i`` (``starts`` non-decreasing, from 0) —
    ``vals[searchsorted(starts, i, "right") - 1]`` without a search:
    each slot adds its step ``vals[k] - vals[k-1]`` at its start and a
    prefix sum carries it forward.  Equal starts (zero-length slots)
    telescope to the last of them, as the search picks; integer
    wrap-around cannot change a telescoped sum."""
    step = vals - jnp.concatenate([jnp.zeros((1,), vals.dtype),
                                   vals[:-1]])
    acc = scatter_rows(jnp.zeros((1, n), vals.dtype), starts, step[None],
                       "add")[0]
    return prefix_sum(acc)


def _lb_enum_size(ecap: int, num_tiles: int) -> int:
    """Edge ids the LB pass enumerates for ``ecap``: the next multiple
    of ``num_tiles``, so that the blocked deal is a bijection of them
    and cannot miss edges."""
    return -(-ecap // num_tiles) * num_tiles


def _lb_pass_impl(g: Graph, values, labels, fmask, hidx, hdeg, hrow_start,
                  total_edges, ecap: int, op: Operator,
                  distribution: str, num_tiles: int, tile_edges: int = 0):
    """The LB executor (Figure 3, SSSP_LB): edge-balanced renumbering.

    Edges of the huge vertices get global ids 0..total_edges-1 via an
    exclusive prefix sum over their degrees; each edge id is mapped back
    to (src, graph edge) by the slot whose prefix-sum entry is the last
    one at or below it — the paper's CSR-preserving trick.  The paper
    finds that slot by binary search; here every id's slot values come
    from one scatter of the slots' steps and a prefix sum
    (:func:`_segment_values`), since a TPU runs each of a search's
    ~log2(H) gather levels over every id.  ``distribution`` controls
    the edge-id -> lane order (cyclic = consecutive lanes process
    consecutive edges; blocked = strided) — Section 4.1 / Figure 4.
    ``tile_edges`` is unused here (XLA has no grid); kept for executor
    signature parity with the Pallas pair.

    The prefix sum and the deal are computed once per round over the
    union frontier's huge bin; ``fmask[:, src]`` recovers which queries
    the edge's source is actually active in (DESIGN.md section 7).

    Named scopes as in :func:`_bin_pass_impl`, plus ``enumerate``: the
    prefix sum, the id -> slot values and the deal.
    """
    v = labels.shape[-1]
    with jax.named_scope("enumerate"):
        start_e = prefix_sum(hdeg) - hdeg              # exclusive prefix
        n_enum = _lb_enum_size(ecap, num_tiles)
        w_per = n_enum // num_tiles
        eid = jnp.arange(n_enum, dtype=jnp.int32)
        # per id in natural order: graph edge = id + (row start - prefix
        # start) of its slot, and the slot's vertex
        graph_e = (_segment_values(start_e, hrow_start - start_e, n_enum)
                   + eid)
        src = _segment_values(start_e, hidx, n_enum)
        if distribution == "blocked":
            # thread T_i gets the contiguous chunk [i*w_per, (i+1)*w_per):
            # lane-major order becomes strided by w_per (Figure 4 right).
            eid = (eid % num_tiles) * w_per + eid // num_tiles
            graph_e, src = graph_e[eid], src[eid]
    with jax.named_scope("edges"):
        emask = eid < total_edges
        graph_e = spread_index(emask, graph_e, g.col_idx.shape[0])
        dst = g.col_idx[graph_e]
        w = g.edge_w[graph_e]
    with jax.named_scope("sources"):
        if op.direction == "push":
            ssafe = spread_index(emask & (src < v), src, v)
            live = fmask[:, ssafe]                     # [B, n_enum]
            cand = op.msg(values[:, ssafe], w[None])
            target = dst
        else:
            # pull: liveness comes from the in-neighbour (``dst`` of the
            # reverse CSR), the anchor ``src`` receives the candidate
            live = fmask[:, dst]                       # [B, n_enum]
            cand = op.msg(values[:, dst], w[None])
            target = src
    with jax.named_scope("combine"):
        return _apply(labels, target, cand, emask, live, op.combine)


_lb_pass = partial(jax.jit, static_argnames=(
    "ecap", "op", "distribution", "num_tiles", "tile_edges"))(_lb_pass_impl)


register_executor(ExecutorPair("xla",
                               bin_host=_bin_pass, bin_jit=_bin_pass_impl,
                               lb_host=_lb_pass, lb_jit=_lb_pass_impl))


# ---------------------------------------------------------------------------
# host-driven round (per-round "kernel launches", bucketed jit)
# ---------------------------------------------------------------------------

def _gather_bin_impl(mask, fidx, deg, row_start, cap: int, fcap: int,
                     v: int):
    """Compact a bin mask into (vidx, deg, row) at capacity ``cap``
    (slots past the bin size become out-of-range sentinels).  One fused
    kernel per (cap, fcap) bucket: the compaction and the three
    selector gathers used to run as ~9 separate dispatches per bin per
    round, which dominated small-frontier rounds — exactly the
    per-round fixed cost the batched/serving engines amortize."""
    sel = compact(mask, cap)                       # slots into fidx
    sel_safe = jnp.where(sel < fcap, sel, 0)
    take = sel < fcap
    return (jnp.where(take, fidx[sel_safe], v),
            jnp.where(take, deg[sel_safe], 0),
            jnp.where(take, row_start[sel_safe], 0))


# bucketed capacities keep the number of distinct (cap, fcap, v) keys
# small for any ONE graph, but a long-lived process touching many
# graphs/configs (the serving deployment, the benchmark sweeps) used to
# grow one compiled executable per key forever; the LRU bound below
# caps that at the _GATHER_BIN_CACHE_CAP hottest buckets
_GATHER_BIN_CACHE_CAP = 64
_GATHER_BIN_CACHE: "OrderedDict[tuple, Callable]" = OrderedDict()


def _gather_bin(mask, fidx, deg, row_start, cap: int, fcap: int, v: int):
    """LRU-bounded jit front of :func:`_gather_bin_impl`: one jitted
    closure per (cap, fcap, v) shape bucket, evicting the least
    recently used bucket (and its compiled executables) past
    ``_GATHER_BIN_CACHE_CAP`` entries."""
    key = (cap, fcap, v)
    fn = _GATHER_BIN_CACHE.pop(key, None)
    if fn is None:
        fn = jax.jit(partial(_gather_bin_impl, cap=cap, fcap=fcap, v=v))
        while len(_GATHER_BIN_CACHE) >= _GATHER_BIN_CACHE_CAP:
            _GATHER_BIN_CACHE.popitem(last=False)
    _GATHER_BIN_CACHE[key] = fn                    # most recently used
    return fn(mask, fidx, deg, row_start)


# the recompile-count gates (tests/test_streaming.py) watch jitted
# fns via _cache_size(); keep that introspection working across the
# LRU front by summing the live closures' trace counts
_gather_bin._cache_size = (                        # type: ignore[attr-defined]
    lambda: sum(f._cache_size() for f in _GATHER_BIN_CACHE.values()))


@partial(jax.jit, static_argnames=("cfg",))
def _host_round_counts(g: Graph, frontier: jax.Array, cfg: BalancerConfig):
    """Every host-side decision scalar of one round, fused into a single
    int32 vector so ``relax`` pays ONE device->host transfer per round
    (instead of one blocking ``int(jnp.sum(...))`` per bin plus the
    frontier count and inspector sums).

    Layout: ``[union_frontier_count,
               (bin_count, bin_max_deg, bin_edge_sum) per bin of
               :func:`host_plan`...,
               huge_count, huge_edge_sum (when the plan has an LB path),
               per-query frontier counts (B entries, batched input only)]``

    A batched ``[B, V]`` frontier is reduced to its union first — the
    bins and the inspector see one frontier for the whole batch
    (DESIGN.md section 7); the per-query counts ride along in the same
    transfer for the instrumentation.  The union mask is returned
    alongside so the caller's compaction reuses this one reduction.
    """
    deg = g.row_ptr[1:] - g.row_ptr[:-1]
    union = union_frontier(frontier)
    plan = host_plan(cfg)
    vals = [count(union)]
    for spec in plan.bins:
        m = spec.mask(deg, union)
        md = jnp.where(m, deg, 0)
        vals += [jnp.sum(m.astype(jnp.int32)), jnp.max(md), jnp.sum(md)]
    if plan.lb != "none":
        hm = plan.lb_mask(deg, union, cfg.threshold)
        vals += [jnp.sum(hm.astype(jnp.int32)),
                 jnp.sum(jnp.where(hm, deg, 0))]
    head = jnp.stack([jnp.asarray(v, jnp.int32) for v in vals])
    if frontier.ndim == 1:
        return head, union
    return jnp.concatenate(
        [head, jnp.sum(frontier.astype(jnp.int32), axis=1)]), union


def _counts_frontier_edges(cnt: np.ndarray, plan: RoundPlan) -> int:
    """Union-frontier out-edge total, reassembled from the fused host
    count layout of :func:`_host_round_counts` (per-bin edge sums plus
    the LB-path sum) — the ``m_f`` input of :func:`resolve_direction`.
    ``plan`` is the :func:`host_plan` the counts were taken for; its
    bins and LB mask partition the frontier's edges for every
    strategy, so the sum is exact."""
    k, total = 1, 0
    for _ in plan.bins:
        total += int(cnt[k + 2])
        k += 3
    if plan.lb != "none":
        total += int(cnt[k + 1])
    return total


class _PullEnum(NamedTuple):
    """Frontier-independent pull-side enumeration of one (graph, plan):
    the reverse CSR plus pre-gathered bin/LB member arrays over every
    vertex with incoming edges, binned by IN-degree into the bins of
    :func:`host_plan` (DESIGN.md section 9).  A pull round gathers at
    each in-edge's source, so its work set never depends on the
    frontier — it is built once per graph x plan (one blocking
    transfer, amortized) and cached on the Graph object, keeping pull
    rounds free of per-round device syncs and per-round gather
    dispatches."""
    rg: Graph
    emask: jax.Array     # bool[V]: in-degree > 0 (the enumeration set)
    bins: tuple          # per plan bin: None | (max_d, edge_sum,
    #                      bvidx, bdeg, brow) at bucketed capacity
    lb: Optional[tuple]  # None | (total, hvidx, hdeg, hrow)


def _pull_plan_key(cfg: BalancerConfig) -> tuple:
    """What a pull enumeration depends on: the bins and LB mode of the
    config's :func:`host_plan`, and the threshold of its LB mask.  The
    direction and deal fields are left out, so push/adaptive variants
    share one cache entry; so do the xla and pallas backends (same
    plan), while ``merge_path``'s plan (no bins, LB = all —
    :func:`effective_plan`) keys its own."""
    plan = host_plan(cfg)
    return (plan.bins, plan.lb, cfg.threshold)


@partial(jax.jit, static_argnames=("plan", "threshold"))
def _plan_masks(deg, valid, plan: RoundPlan, threshold: int):
    """Every bin's membership mask over a compacted frontier, then the
    LB path's (when the plan has one): one program per (plan, frontier
    bucket) in place of a few eager element-wise dispatches per bin."""
    masks = tuple(spec.mask(deg, valid) for spec in plan.bins)
    if plan.lb != "none":
        masks += (plan.lb_mask(deg, valid, threshold),)
    return masks


def _assemble_bins(cnt: np.ndarray, plan: RoundPlan,
                   cfg: BalancerConfig, fidx, deg, row_start, valid,
                   fcap: int, v: int):
    """Gather the bin / LB member arrays of ``plan`` (the config's
    :func:`host_plan`) named by the fused host count vector (the
    :func:`_host_round_counts` layout: per-bin triplets, then the
    inspector pair).  Returns ``(bins, lb)`` in the
    :func:`_run_plan_host` format — the ONE assembly shared by the push
    round (per round, over the frontier) and the cached pull
    enumeration (once per graph), so the count layout can never
    desynchronize between them."""
    bins, k = [], 1
    masks = _plan_masks(deg, valid, plan, cfg.threshold)
    for spec, mask in zip(plan.bins, masks):
        n, max_d, edge_sum = int(cnt[k]), int(cnt[k + 1]), int(cnt[k + 2])
        k += 3
        if n == 0:
            bins.append(None)
            continue
        bvidx, bdeg, brow = _gather_bin(mask, fidx, deg, row_start,
                                        next_bucket(n), fcap, v)
        bins.append((max_d, edge_sum, bvidx, bdeg, brow))
    lb = None
    if plan.lb != "none":
        # ---- inspector (Section 4.1): is the huge bin non-empty? ----
        n_huge, total = int(cnt[k]), int(cnt[k + 1])
        if n_huge > 0 and total > 0:
            hvidx, hdeg, hrow = _gather_bin(masks[-1], fidx, deg, row_start,
                                            next_bucket(n_huge), fcap, v)
            lb = (total, hvidx, hdeg, hrow)
    return tuple(bins), lb


def _build_pull_enum(g: Graph, cfg: BalancerConfig) -> _PullEnum:
    """Materialize the pull-side enumeration (see :class:`_PullEnum`)."""
    rg = g.reverse()
    v = rg.num_vertices
    emask = (rg.row_ptr[1:] - rg.row_ptr[:-1]) > 0
    cnt, union = _host_round_counts(rg, emask, cfg)
    cnt = np.asarray(cnt)
    fcap = next_bucket(int(cnt[0]))
    fidx = compact(union, fcap)
    deg, row_start, valid = _frontier_meta(rg, fidx)
    bins, lb = _assemble_bins(cnt, host_plan(cfg), cfg, fidx, deg,
                              row_start, valid, fcap, v)
    return _PullEnum(rg, emask, bins, lb)


def _pull_enum(g: Graph, cfg: BalancerConfig) -> _PullEnum:
    """Cached :func:`_build_pull_enum` (on the Graph object, keyed by
    ``g.version`` plus the plan-relevant cfg fields).

    The version component is the invalidation hook for streaming
    mutations (DESIGN.md section 10): an in-place topology change bumps
    ``g.version``, so every enumeration built for the old topology
    misses and is dropped — without it a pull round after a mutation
    would keep binning the stale reverse CSR."""
    cache = g.__dict__.get("_pull_enum_cache")
    if cache is None:
        cache = {}
        object.__setattr__(g, "_pull_enum_cache", cache)
    key = (g.version,) + _pull_plan_key(cfg)
    if key not in cache:
        for stale in [k for k in cache if k[0] != g.version]:
            del cache[stale]          # unreachable versions: drop
        cache[key] = _build_pull_enum(g, cfg)
    return cache[key]


def _run_plan_host(gr: Graph, values, labels, fmask, plan: RoundPlan,
                   cfg: BalancerConfig, op: Operator, ex: ExecutorPair,
                   bins, lb, stats) -> jax.Array:
    """Drive one host round's executor launches from pre-gathered
    bin/LB member arrays — shared by the push path (members gathered
    from this round's frontier) and the pull path (members cached per
    graph by :func:`_pull_enum`).  ``stats`` is the mutable RoundStats
    dict or None.  Bumps the slot counters (:func:`counter_snapshot`)
    from the bins' host counts and static capacities."""
    for spec, entry in zip(plan.bins, bins):
        if entry is None:
            continue
        max_d, edge_sum, bvidx, bdeg, brow = entry
        passes = max(1, -(-max_d // spec.width))
        for c in range(passes):
            labels = ex.bin_host(gr, values, labels, fmask, bvidx,
                                 bdeg, brow, spec.width, op, c)
        _COUNTERS["bin_slots"] += passes * bvidx.shape[0] * spec.width
        _COUNTERS["bin_edges"] += edge_sum
        if stats is not None:
            stats["edges_twc"] += edge_sum
    if lb is not None:
        total, hvidx, hdeg, hrow = lb
        ecap = next_bucket(total, minimum=cfg.lb_tile_edges)
        labels = ex.lb_host(gr, values, labels, fmask, hvidx, hdeg,
                            hrow, jnp.int32(total), ecap, op,
                            cfg.distribution, cfg.num_tiles,
                            cfg.lb_tile_edges)
        _COUNTERS["lb_slots"] += _lb_enum_size(ecap, cfg.num_tiles)
        _COUNTERS["lb_edges"] += total
        if stats is not None:
            stats["edges_lb"] = total
            stats["lb_invoked"] = True
    return labels


def relax(g: Graph, values: jax.Array, labels: jax.Array,
          frontier: jax.Array, cfg: BalancerConfig, op: Operator,
          collect_stats: bool = False, return_active: bool = False):
    """One round: apply ``op`` along all edges of active vertices.

    Returns (new_labels, RoundStats|None).  ``values`` is the per-vertex
    quantity being propagated (may alias ``labels``); ``labels`` is the
    array updated by scatter-combine.

    Batched form (DESIGN.md section 7): with ``labels``/``values``/
    ``frontier`` of shape ``[B, V]`` the round serves B independent
    queries from ONE set of launches — bins, inspector, and the LB deal
    are planned on the union frontier and the executors recover
    per-query activity from the ``[B, V]`` mask.  The returned labels
    keep the batch axis.

    Traversal direction (DESIGN.md section 9): with
    ``cfg.direction="pull"`` (or ``"adaptive"`` resolving to pull for
    this round — :func:`resolve_direction` over the same fused host
    counts, no extra sync) the round runs the operator's pull twin over
    the cached reverse CSR: enumeration covers every vertex with
    incoming edges (binned by in-degree, cached per graph), the
    executors gather value AND activity at each in-edge's source and
    combine at the anchor.  Only push ``min``-combine operators may be
    flipped; the result is bitwise equal to the push round's.

    ``return_active=True`` appends a host ``bool[B]`` (``bool[1]`` for
    the un-batched form) marking which rows entered the round with a
    non-empty frontier — per-slot liveness instrumentation for round
    loops over batched state (DESIGN.md section 8).  It is sliced out
    of the fused host-transfer the round already performs, so
    observing it costs no extra device round-trip.
    """
    batched = labels.ndim == 2
    # the round's phases, as profiler spans (DESIGN.md section 11); each
    # holds all of its host work, so that a device idle gap inside the
    # round falls in one of them
    with jax.profiler.TraceAnnotation("graph.counts"):
        if not batched:
            values, labels, frontier = (values[None], labels[None],
                                        frontier[None])
        b, v = labels.shape
        plan = host_plan(cfg)
        # validate direction x operator up front (even when adaptive
        # ends up resolving to push every round, a bad pairing is a
        # config bug)
        pull_op = as_pull(op) if cfg.direction != "push" else None
        cnt, union = _host_round_counts(g, frontier, cfg)
        cnt = np.asarray(cnt)
        _note_host_transfer()          # THE per-round host sync point
        nf = int(cnt[0])                               # union size
        active = cnt[-b:] > 0
        if nf == 0:
            out = ((labels if batched else labels[0]), None)
            return out + (active,) if return_active else out

    with jax.profiler.TraceAnnotation("graph.plan"):
        m_f = _counts_frontier_edges(cnt, plan)
        direction = resolve_direction(cfg, nf, m_f, v, g.num_edges)
        ex = get_executor(cfg.executor)
        stats = dict(frontier_size=nf, edges_twc=0, edges_lb=0,
                     lb_invoked=False,
                     frontier_per_query=cnt[-b:].astype(np.int64),
                     direction=direction,
                     frontier_edges=m_f,
                     host_transfers=1) if collect_stats else None
        if direction == "pull":
            pe = _pull_enum(g, cfg)
            gr, rop, bins, lb = pe.rg, pull_op, pe.bins, pe.lb
        else:
            fcap = next_bucket(nf)
            fidx = compact(union, fcap)
            deg, row_start, valid = _frontier_meta(g, fidx)
            bins, lb = _assemble_bins(cnt, plan, cfg, fidx, deg,
                                      row_start, valid, fcap, v)
            gr, rop = g, op
    with jax.profiler.TraceAnnotation("graph.passes"):
        labels = _run_plan_host(gr, values, labels, frontier, plan, cfg,
                                rop, ex, bins, lb, stats)
        labels = labels if batched else labels[0]
    out = (labels, RoundStats(**stats) if stats is not None else None)
    return out + (active,) if return_active else out


# ---------------------------------------------------------------------------
# fully-jit SPMD round (for shard_map / distributed execution)
# ---------------------------------------------------------------------------

def _relax_spmd_impl(g: Graph, values: jax.Array, labels: jax.Array,
                     frontier: jax.Array, cfg: BalancerConfig,
                     op: Operator, collect_stats: bool = False,
                     return_dirty: bool = False,
                     emask: Optional[jax.Array] = None):
    """Static-shape ALB round: capacities fixed at V/E, LB path guarded
    by ``lax.cond``, unbounded bins driven by ``lax.while_loop`` — the
    SPMD realization of the inspector-executor split.  Runs the same
    :func:`make_plan` output through the registry's fully-jit executor
    entries, so all four strategies (and both the XLA and Pallas
    backends) are available inside ``shard_map``.

    Returns ``labels``, extended to ``(labels, RoundStatsDev)`` with
    ``collect_stats=True`` and/or ``(..., dirty)`` with
    ``return_dirty=True`` — ``dirty`` is the jit-safe changed-label
    bitvector the master/mirror sync exchanges over (DESIGN.md
    section 6).

    Like :func:`relax`, accepts batched ``[B, V]`` labels/values/
    frontier (DESIGN.md section 7): the static-capacity enumeration,
    the ``lax.while_loop`` chunk driver, and the ``lax.cond`` inspector
    all run once on the union frontier for the whole batch; ``dirty``
    and the returned labels keep the batch axis.

    ``emask`` (DESIGN.md section 9) decouples the *enumeration* set
    from the frontier: a pull round passes the reverse CSR as ``g``,
    the pull twin of its operator, and ``emask`` = the ``bool[V]``
    in-degree mask — vertices are enumerated from ``emask`` while the
    executors still gather per-query activity from ``frontier``.
    ``None`` (the default, and every push round) enumerates the union
    frontier as before.  :func:`relax_spmd_directed` wraps this with
    the per-round direction resolution, and the fused traversal loop
    (:func:`run_fused`) inlines this body — it is a plain traceable
    function; ``relax_spmd`` is its top-level jitted form.
    """
    batched = labels.ndim == 2
    if not batched:
        values, labels, frontier = (values[None], labels[None],
                                    frontier[None])
    labels_in = labels
    v = labels.shape[-1]
    union = union_frontier(frontier)
    fidx = compact(union if emask is None else emask, v)
    deg, row_start, valid = _frontier_meta(g, fidx)

    ex = get_executor(cfg.executor)
    plan = effective_plan(cfg)
    edges_twc = jnp.int32(0)

    for spec in plan.bins:
        mask = spec.mask(deg, valid)
        bvidx = jnp.where(mask, fidx, v)
        bdeg = jnp.where(mask, deg, 0)
        brow = jnp.where(mask, row_start, 0)
        passes = spec.static_passes()
        if passes is not None:
            for c in range(passes):
                labels = ex.bin_jit(g, values, labels, frontier, bvidx,
                                    bdeg, brow, spec.width, op,
                                    jnp.int32(c))
        else:
            # unbounded bin: data-dependent pass count (0 when empty)
            max_d = jnp.max(bdeg)

            def cond(carry, _w=spec.width, _m=max_d):
                c, _ = carry
                return c * _w < _m

            def body(carry, _s=spec, _b=(bvidx, bdeg, brow)):
                c, lab = carry
                lab = ex.bin_jit(g, values, lab, frontier, *_b,
                                 _s.width, op, c)
                return c + 1, lab

            _, labels = jax.lax.while_loop(
                cond, body, (jnp.int32(0), labels))
        if collect_stats:
            edges_twc = edges_twc + jnp.sum(bdeg).astype(jnp.int32)

    edges_lb = jnp.int32(0)
    lb_invoked = jnp.asarray(False)
    if plan.lb != "none":
        hmask = plan.lb_mask(deg, valid, cfg.threshold)
        n_huge = jnp.sum(hmask.astype(jnp.int32))
        ecap = g.col_idx.shape[0]
        hvidx = jnp.where(hmask, fidx, v)
        hdeg = jnp.where(hmask, deg, 0)
        hrow = jnp.where(hmask, row_start, 0)
        total = jnp.sum(hdeg)

        def lb_branch(labels):
            new = ex.lb_jit(g, values, labels, frontier, hvidx, hdeg,
                            hrow, total, ecap, op, cfg.distribution,
                            cfg.num_tiles, cfg.lb_tile_edges)
            return new, total.astype(jnp.int32)

        def skip_branch(labels):
            return labels, jnp.int32(0)

        labels, edges_lb = jax.lax.cond(
            n_huge > 0, lb_branch, skip_branch, labels)
        lb_invoked = n_huge > 0

    outs = (labels if batched else labels[0],)
    if collect_stats:
        outs += (RoundStatsDev(
            frontier_size=jnp.sum(union.astype(jnp.int32)),
            edges_twc=edges_twc, edges_lb=edges_lb,
            lb_invoked=lb_invoked,
            mirrors_synced=jnp.int32(0), bytes_synced=jnp.int32(0),
            frontier_per_query=jnp.sum(frontier.astype(jnp.int32),
                                       axis=1)),)
    if return_dirty:
        dirty = dirty_mask(labels_in, labels)
        outs += (dirty if batched else dirty[0],)
    return outs[0] if len(outs) == 1 else outs


relax_spmd = partial(jax.jit, static_argnames=(
    "cfg", "op", "collect_stats", "return_dirty"))(_relax_spmd_impl)


# ---------------------------------------------------------------------------
# device-resident planning: direction resolved by lax.cond over the
# on-device counts, whole traversals fused into one lax.while_loop
# ---------------------------------------------------------------------------

def relax_fused_round(g: Graph, rg: Optional[Graph],
                      emask: Optional[jax.Array], values: jax.Array,
                      labels: jax.Array, frontier: jax.Array,
                      cfg: BalancerConfig, op: Operator,
                      pull_op: Optional[Operator] = None,
                      collect_stats: bool = False):
    """One balancer round with the *entire* inspector on device — the
    trace-safe round primitive of the fused traversal loop (DESIGN.md
    section 11).

    The union-frontier count ``n_f`` and out-edge total ``m_f`` are
    computed as device scalars, the Beamer direction rule becomes a
    ``lax.cond`` branch selector (:func:`resolve_direction_device`),
    and each branch inlines the static-shape SPMD round
    (:func:`_relax_spmd_impl`) — push on ``g``, pull on the cached
    reverse CSR ``rg`` with its in-degree ``emask``.  Nothing here
    touches the host, so the caller can wrap any number of these rounds
    in one ``lax.while_loop``.

    Inputs are batched ``[B, V]`` (callers canonicalize); ``rg`` /
    ``emask`` / ``pull_op`` may be None for ``direction="push"``
    configs.  Returns ``(labels, is_pull, n_f, m_f, stats)`` — all
    device values; ``stats`` is a :class:`RoundStatsDev` with
    ``frontier_edges`` / ``is_pull`` filled in (None unless
    ``collect_stats``)."""
    v = labels.shape[-1]
    deg = g.row_ptr[1:] - g.row_ptr[:-1]
    union = union_frontier(frontier)
    nf = count(union)
    m_f = jnp.sum(jnp.where(union, deg, 0)).astype(jnp.int32)
    is_pull = resolve_direction_device(cfg, nf, m_f, v, g.num_edges)
    if cfg.direction == "push":
        out = _relax_spmd_impl(g, values, labels, frontier, cfg, op,
                               collect_stats=collect_stats)
    elif cfg.direction == "pull":
        out = _relax_spmd_impl(rg, values, labels, frontier, cfg,
                               pull_op, collect_stats=collect_stats,
                               emask=emask)
    else:
        out = jax.lax.cond(
            is_pull,
            lambda val, lab, fr: _relax_spmd_impl(
                rg, val, lab, fr, cfg, pull_op,
                collect_stats=collect_stats, emask=emask),
            lambda val, lab, fr: _relax_spmd_impl(
                g, val, lab, fr, cfg, op, collect_stats=collect_stats),
            values, labels, frontier)
    if collect_stats:
        labels_out, st = out
        st = st._replace(frontier_edges=m_f, is_pull=is_pull)
    else:
        labels_out, st = out, None
    return labels_out, is_pull, nf, m_f, st


def _fused_stats_init(max_rounds: int, b: int) -> RoundStatsDev:
    """Device-resident per-round stat buffers of a fused traversal:
    a :class:`RoundStatsDev` whose every leaf gained a leading
    ``[max_rounds]`` round axis, zero-filled."""
    z = partial(jnp.zeros, dtype=jnp.int32)
    return RoundStatsDev(
        frontier_size=z((max_rounds,)),
        edges_twc=z((max_rounds,)), edges_lb=z((max_rounds,)),
        lb_invoked=jnp.zeros((max_rounds,), bool),
        mirrors_synced=z((max_rounds,)), bytes_synced=z((max_rounds,)),
        bytes_wire=z((max_rounds,)),
        frontier_per_query=z((max_rounds, b)),
        frontier_edges=z((max_rounds,)),
        is_pull=jnp.zeros((max_rounds,), bool))


@partial(jax.jit, static_argnames=("cfg", "op", "pull_op", "max_rounds",
                                   "collect_stats"))
def _run_fused_loop(g: Graph, rg, emask, labels, frontier,
                    cfg: BalancerConfig, op: Operator, pull_op,
                    max_rounds: int, collect_stats: bool):
    """The fused min-combine convergence loop: ONE ``lax.while_loop``
    whose body is :func:`relax_fused_round` plus the ``new < old``
    frontier update; stats rows are written into the device buffers at
    the round index.  The loop condition probes the union frontier on
    device, so between dispatch and the caller's final fetch no value
    ever crosses to the host."""
    st0 = (_fused_stats_init(max_rounds, labels.shape[0])
           if collect_stats else None)

    def cond(carry):
        r, lab, fr, st = carry
        return (r < max_rounds) & jnp.any(fr)

    def body(carry):
        r, lab, fr, st = carry
        new, _, _, _, row = relax_fused_round(
            g, rg, emask, lab, lab, fr, cfg, op, pull_op, collect_stats)
        if collect_stats:
            st = jax.tree_util.tree_map(
                lambda buf, x: buf.at[r].set(x), st, row)  # repro: allow[scatter-determinism] -- round index r is unique per iteration, no duplicate targets
        return r + 1, new, new < lab, st

    r, labels, frontier, st = jax.lax.while_loop(
        cond, body, (jnp.int32(0), labels, frontier, st0))
    return labels, frontier, r, st


def run_fused(g: Graph, labels: jax.Array, frontier: jax.Array,
              cfg: BalancerConfig, op: Operator,
              max_rounds: int = 10_000, collect_stats: bool = False):
    """Run a whole min-combine traversal as ONE fused device loop —
    zero per-round host syncs (DESIGN.md section 11).

    Bin selection, the huge-bin inspector, and the push/pull direction
    rule all run on device (:func:`relax_fused_round`), so the
    multi-round loop needs no host round-trips: the only transfers are
    the dispatch of this call and whatever the caller fetches from the
    result.  Accepts ``[V]`` or batched ``[B, V]`` state like
    :func:`relax`.  The one-time pull enumeration (``direction`` pull /
    adaptive) is built before dispatch and cached per graph.

    Returns ``(labels, frontier, rounds, stats)`` — ``rounds`` is a
    device scalar and ``stats`` the device-accumulated
    :class:`RoundStatsDev` buffers (None unless ``collect_stats``);
    materialize them with :func:`fused_stats_host` once converged."""
    if op.combine != "min":
        raise ValueError(f"run_fused drives min-combine loops; got "
                         f"{op.name} (combine={op.combine!r})")
    batched = labels.ndim == 2
    lab = labels if batched else labels[None]
    fr = frontier if batched else frontier[None]
    pull_op = as_pull(op) if cfg.direction != "push" else None
    if cfg.direction != "push":
        pe = _pull_enum(g, cfg)
        rg, emask = pe.rg, pe.emask
    else:
        rg, emask = None, None
    lab, fr, r, st = _run_fused_loop(g, rg, emask, lab, fr, cfg=cfg,
                                     op=op, pull_op=pull_op,
                                     max_rounds=int(max_rounds),
                                     collect_stats=collect_stats)
    if not batched:
        lab, fr = lab[0], fr[0]
    return lab, fr, r, st


def fused_stats_host(st: Optional[RoundStatsDev], rounds: int):
    """Materialize a fused traversal's device-accumulated stat buffers
    as the usual per-round ``List[RoundStats]`` — ONE transfer for the
    whole traversal, after convergence (vs one per round in host/spmd
    mode).  ``rounds`` (the loop's round count) selects the filled
    prefix of the ``[max_rounds]`` buffers; fused rounds report
    ``host_transfers=0`` by construction."""
    if st is None:
        return None
    host = jax.tree_util.tree_map(np.asarray, st)
    return [RoundStats.from_device(
                RoundStatsDev(*[leaf[r] for leaf in host]))
            for r in range(int(rounds))]


@partial(jax.jit, static_argnames=("cfg", "op", "pull_op",
                                   "collect_stats"))
def _directed_round_jit(g: Graph, rg, emask, values, labels, frontier,
                        cfg: BalancerConfig, op: Operator, pull_op,
                        collect_stats: bool):
    """One device-directed round plus the per-row liveness of the
    entering frontier — the jitted body behind
    :func:`relax_spmd_directed`."""
    labels_out, is_pull, nf, m_f, st = relax_fused_round(
        g, rg, emask, values, labels, frontier, cfg, op, pull_op,
        collect_stats)
    return labels_out, is_pull, m_f, jnp.any(frontier, axis=-1), st


def relax_spmd_directed(g: Graph, values: jax.Array, labels: jax.Array,
                        frontier: jax.Array, cfg: BalancerConfig,
                        op: Operator, collect_stats: bool = False,
                        return_active: bool = False):
    """Direction-aware fully-jit round (DESIGN.md section 9): the round
    primitive behind ``mode="spmd"`` in the app drivers.

    The direction choice now lives on device — the same
    ``lax.cond``-over-device-counts path the fused loop uses
    (:func:`relax_fused_round`), so an ``adaptive`` config no longer
    pays a host count transfer to *decide*; the host-driven loop around
    this round still syncs once per round to *observe* liveness and
    stats, and only when it asks for them (``return_active`` /
    ``collect_stats``).

    Returns ``(labels, RoundStats|None)`` — host stats with
    ``direction`` and the push-side ``frontier_edges`` filled in —
    extended by a host ``bool[B]`` liveness vector when
    ``return_active=True``."""
    batched = labels.ndim == 2
    if not batched:
        values, labels, frontier = (values[None], labels[None],
                                    frontier[None])
    pull_op = as_pull(op) if cfg.direction != "push" else None
    if cfg.direction != "push":
        pe = _pull_enum(g, cfg)
        rg, emask = pe.rg, pe.emask
    else:
        rg, emask = None, None
    labels_out, is_pull, m_f, active_dev, st_dev = _directed_round_jit(
        g, rg, emask, values, labels, frontier, cfg=cfg, op=op,
        pull_op=pull_op, collect_stats=collect_stats)
    st = active = None
    if collect_stats or return_active:
        # ONE blocking sync for everything the host loop observes
        is_pull_h, m_f_h, active, st_h = jax.device_get(
            (is_pull, m_f, active_dev, st_dev))
        _note_host_transfer()
        active = np.atleast_1d(active)
        if collect_stats:
            st = RoundStats.from_device(st_h)._replace(
                direction="pull" if bool(is_pull_h) else "push",
                frontier_edges=int(m_f_h), host_transfers=1)
    labels_out = labels_out if batched else labels_out[0]
    result = (labels_out, st)
    return result + (active,) if return_active else result
