"""jit'd wrappers: Pallas mapping kernels + XLA gather/scatter epilogue.

These are the ``use_pallas=True`` implementations of the two hot paths
in core/balancer.py.  The mapping (searchsorted / tile expansion) runs
in the Pallas kernel; the irregular HBM traffic (col_idx gather,
scatter-combine into labels) runs in XLA, which lowers it to native TPU
gather/scatter — see edge_lb.py for the design rationale.

Each path ships two entries, registered with the executor registry in
core/balancer.py (DESIGN.md section 3):

* ``twc_bin_apply`` / ``edge_lb_apply`` — host-driven entries: top-level
  jitted, shapes are the per-round *bucketed* capacities chosen by
  ``relax``; one compilation per bucket.
* ``twc_bin_apply_static`` / ``edge_lb_apply_static`` — fully-jit
  entries for ``relax_spmd``: plain functions meant to be traced inside
  an enclosing ``jit``/``shard_map``; capacities are static (V for the
  bins, E for the LB span), the chunk index is a traced scalar so a
  ``lax.while_loop`` can drive unbounded bins.

All entries are **batched** (DESIGN.md section 7): ``values`` /
``labels`` / ``fmask`` carry a leading query axis ``[B, V]`` while the
vertex/edge enumeration stays batch-shared.  The mapping kernel
therefore runs ONCE per round for the whole batch — it emits the
(graph_edge, anchor/slot, mask) tiles of the union frontier — and the
XLA epilogue re-gathers per-query values / activity from the ``[B, V]``
arrays before the batched scatter-combine.  (The kernel's own value
output is only a single query's view and is ignored here.)
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# balancer imports this module only lazily (get_executor), so sharing
# its batched scatter-combine epilogue creates no import cycle — both
# backends neutralize inactive (vertex, query) slots with the exact
# same combiner-identity rule (balancer.combine_neutral)
from repro.core.balancer import _apply
from repro.core.frontier import prefix_sum, spread_index

from . import edge_lb as _edge_lb
from . import merge_path as _merge_path
from . import twc_gather as _twc


# ---------------------------------------------------------------------------
# LB executor (edge-balanced renumbering)
# ---------------------------------------------------------------------------

def edge_lb_apply_static(g, values, labels, fmask, hvidx, hdeg, hrow,
                         total, ecap: int, op, distribution: str,
                         num_tiles: int, tile_edges: int):
    """Fully-jit LB entry: trace-safe body (no own jit wrapper)."""
    v = labels.shape[-1]
    start_e = prefix_sum(hdeg) - hdeg
    vsafe = jnp.where(hvidx < v, hvidx, 0)
    hval = values[0, vsafe]            # kernel value plumbing: batch 0
    ge, j, _, mask = _edge_lb.edge_lb_map(
        start_e, hrow, hval, total, ecap,
        tile_edges=tile_edges, distribution=distribution,
        num_tiles=num_tiles)
    ge = spread_index(mask, ge, g.col_idx.shape[0])
    dst = g.col_idx[ge]
    w = g.edge_w[ge]
    j = jnp.clip(j, 0, hvidx.shape[0] - 1)
    src = jnp.where(hvidx.shape[0] > 0, hvidx[j], 0)
    ssafe = spread_index(mask & (src < v), src, v)
    if op.direction == "push":
        live = fmask[:, ssafe]                           # [B, n]
        cand = op.msg(values[:, ssafe], w[None])
        return _apply(labels, dst, cand, mask, live, op.combine)
    # pull: value AND activity gathered at the in-neighbour (``dst`` in
    # the reverse CSR), combined at the anchor (DESIGN.md section 9)
    live = fmask[:, dst]                                 # [B, n]
    cand = op.msg(values[:, dst], w[None])
    return _apply(labels, src, cand, mask, live, op.combine)


@partial(jax.jit,
         static_argnames=("ecap", "op", "distribution", "num_tiles",
                          "tile_edges"))
def edge_lb_apply(g, values, labels, fmask, hvidx, hdeg, hrow, total,
                  ecap: int, op, distribution: str, num_tiles: int,
                  tile_edges: int):
    """Host-driven LB entry: jitted per (ecap, op, ...) bucket."""
    return edge_lb_apply_static(g, values, labels, fmask, hvidx, hdeg,
                                hrow, total, ecap, op, distribution,
                                num_tiles, tile_edges)


# ---------------------------------------------------------------------------
# Merge-path executor (equal-work edge tiles, no bins, no inspector)
# ---------------------------------------------------------------------------

def merge_path_apply_static(g, values, labels, fmask, hvidx, hdeg, hrow,
                            total, ecap: int, op, distribution: str,
                            num_tiles: int, tile_edges: int):
    """Fully-jit merge-path entry: trace-safe body (no own jit wrapper).

    Signature-compatible with the LB entries so the executor registry
    can route the whole frontier through it (``effective_plan``
    collapses the plan to LB-all under this backend).  The co-ranked
    equal-work deal is contiguous by construction, so ``distribution``
    and ``num_tiles`` do not apply and are ignored."""
    del distribution, num_tiles
    v = labels.shape[-1]
    start_e = prefix_sum(hdeg) - hdeg
    ge, j, mask = _merge_path.merge_path_map(
        start_e, hrow, total, ecap, tile_edges=tile_edges)
    ge = spread_index(mask, ge, g.col_idx.shape[0])
    dst = g.col_idx[ge]
    w = g.edge_w[ge]
    j = jnp.clip(j, 0, hvidx.shape[0] - 1)
    src = jnp.where(hvidx.shape[0] > 0, hvidx[j], 0)
    ssafe = spread_index(mask & (src < v), src, v)
    if op.direction == "push":
        live = fmask[:, ssafe]                           # [B, n]
        cand = op.msg(values[:, ssafe], w[None])
        return _apply(labels, dst, cand, mask, live, op.combine)
    # pull: value AND activity gathered at the in-neighbour (``dst`` in
    # the reverse CSR), combined at the anchor (DESIGN.md section 9)
    live = fmask[:, dst]                                 # [B, n]
    cand = op.msg(values[:, dst], w[None])
    return _apply(labels, src, cand, mask, live, op.combine)


@partial(jax.jit,
         static_argnames=("ecap", "op", "distribution", "num_tiles",
                          "tile_edges"))
def merge_path_apply(g, values, labels, fmask, hvidx, hdeg, hrow, total,
                     ecap: int, op, distribution: str, num_tiles: int,
                     tile_edges: int):
    """Host-driven merge-path entry: jitted per (ecap, op, ...) bucket."""
    return merge_path_apply_static(g, values, labels, fmask, hvidx,
                                   hdeg, hrow, total, ecap, op,
                                   distribution, num_tiles, tile_edges)


def merge_path_no_bins(*_args, **_kwargs):
    """Bin-entry placeholder of the merge-path pair: the backend's plan
    has no degree bins (``effective_plan``), so reaching this is a
    planner bug, not a fallback."""
    raise RuntimeError("merge_path backend plans no degree bins; "
                       "its bin executor entries are unreachable")


# ---------------------------------------------------------------------------
# Bin executor (vertex-binned TWC-analog passes)
# ---------------------------------------------------------------------------

def twc_bin_apply_static(g, values, labels, fmask, bvidx, bdeg, brow,
                         width: int, op, chunk):
    """Fully-jit bin entry: ``chunk`` may be a traced int32 scalar."""
    v = labels.shape[-1]
    vsafe = jnp.where(bvidx < v, bvidx, 0)
    bval = values[0, vsafe]            # kernel value plumbing: batch 0
    ge, anchor, _, mask = _twc.twc_bin_map(
        bvidx, bdeg, brow, bval, width=width, chunk=chunk,
        sentinel=v)
    ge = spread_index(mask, ge, g.col_idx.shape[0])
    dst = g.col_idx[ge]
    w = g.edge_w[ge]
    # the kernel may pad the bin to its vertex-tile size: recover the
    # per-row vertex ids from the anchor tiles (rows are constant)
    row_vid = anchor[:, 0]                               # [N] (pad = v)
    rsafe = jnp.where(row_vid < v, row_vid, 0)
    if op.direction == "push":
        live = fmask[:, rsafe][:, :, None]               # [B, N, 1]
        val = values[:, rsafe][:, :, None]               # [B, N, 1]
        cand = op.msg(val, w[None])
        return _apply(labels, dst, cand, mask, live, op.combine)
    # pull: value AND activity gathered at the in-neighbour (``dst`` in
    # the reverse CSR), combined at the anchor (DESIGN.md section 9)
    live = fmask[:, dst]                                 # [B, N, W]
    cand = op.msg(values[:, dst], w[None])
    return _apply(labels, anchor, cand, mask, live, op.combine)


@partial(jax.jit, static_argnames=("width", "op"))
def twc_bin_apply(g, values, labels, fmask, bvidx, bdeg, brow,
                  width: int, op, chunk):
    """Host-driven bin entry: jitted per (width, op) bucket."""
    return twc_bin_apply_static(g, values, labels, fmask, bvidx, bdeg,
                                brow, width, op, chunk)
