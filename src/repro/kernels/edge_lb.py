"""Pallas TPU kernel for the LB executor (the paper's SSSP_LB kernel).

The kernel implements the edge-balanced renumbering: given the huge
vertices' exclusive degree prefix sum (``start_e``), their CSR row
starts and propagated values, every grid step processes one tile of
global edge ids and recovers, per edge,

    j        = searchsorted(start_e, eid)      (Fig. 4's binary search)
    graph_e  = row_start[j] + (eid - start_e[j])
    src, val = vertex id and propagated value of slot j

GPU -> TPU mapping: one grid step = one "thread block"; the (R, 128)
edge tile = the block's lanes.  A tile holds a *contiguous* run of
edge ids (the paper's ``cyclic`` deal), so it touches a short window
of slots: the co-ranks ``lo = rank(first id)`` and ``hi = rank(last
id)`` bound it.  The search is split accordingly:

1. outside the kernel (XLA), zero-degree slots are dropped — they own
   no edge, and without them a window holds at most ``tile_edges``
   slots — and the per-tile co-ranks are found by a scalar
   ``searchsorted`` over the compacted prefix sum;
2. ``lo``/``hi`` arrive as scalar-prefetch arguments; they select the
   two ``tile_edges``-slot blocks of the compacted arrays that cover
   the window, which the pipeline copies into SMEM;
3. the kernel walks the window's slots in order and, per slot, does a
   vector compare-and-select over the whole edge tile (the last slot
   whose start is <= the edge id wins — the searchsorted-right rule).

No vector gather runs in the kernel and no per-frontier array is held
in fast memory: the work per tile is bounded by the slots the tile
touches, which is the TPU realization of the paper's "binary search
path stays in cache" argument.  ``blocked`` — lane ids strided by
``w_per``, the paper's Figure 4/8 counterpoint — is the same mapping
read through the blocked permutation of the deal (an XLA gather over
the kernel's output).

Edge-id enumeration contract (shared with the XLA ``_lb_pass`` in
core/balancer.py): ``w_per = ceil(ecap / num_tiles)`` and the blocked
permutation is a bijection of exactly ``span = w_per * num_tiles`` ids.
The output covers ``span`` rounded up to the tile size; positions past
``span`` are masked out *before* the permutation is applied, so
blocked mode can neither miss nor double-process an edge regardless of
how ``num_tiles`` divides the padded extent (double-processing would
corrupt add-combine operators).

The heavy irregular traffic (col_idx[graph_e] gathers from HBM and the
scatter-min into the label array) is left to XLA's native gather /
scatter, which the TPU does well; the kernel produces the
(graph_e, slot) pairs.  On a TPU the kernel is compiled; elsewhere it
runs in the Pallas interpreter (``repro.kernels.pallas_call``), which
the tests check against ref.py.

Batched queries (DESIGN.md section 7): the mapping is a pure function
of the union frontier's huge bin — (graph_e, slot, mask) are shared by
every query in a batch — so ``ops.edge_lb_apply*`` launch this kernel
ONCE per round regardless of the batch size and re-gather per-query
values in the XLA epilogue; the ``val`` output then carries a single
query's view and is ignored there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.frontier import compact

from . import pallas_call

_LANES = 128
_NO_START = jnp.iinfo(jnp.int32).max     # start of a padding slot


def _kernel(lo_ref, hi_ref, total_ref, s0_ref, s1_ref, d0_ref, d1_ref,
            j0_ref, j1_ref, ge_ref, slot_ref, msk_ref, *, tile_r: int):
    t = pl.program_id(0)
    tile = tile_r * _LANES
    eid = (t * tile
           + jax.lax.broadcasted_iota(jnp.int32, (tile_r, _LANES), 0)
           * _LANES
           + jax.lax.broadcasted_iota(jnp.int32, (tile_r, _LANES), 1))
    base = lo_ref[t] // tile * tile       # first slot of the window

    def at(first, second, k):             # window slot k, from SMEM
        return jnp.where(k < tile, first[jnp.minimum(k, tile - 1)],
                         second[jnp.maximum(k - tile, 0)])

    lo = lo_ref[t] - base
    jv = jnp.full(eid.shape, at(j0_ref, j1_ref, lo), jnp.int32)
    dv = jnp.full(eid.shape, at(d0_ref, d1_ref, lo), jnp.int32)

    def body(k, carry):
        jv, dv = carry
        hit = at(s0_ref, s1_ref, k) <= eid
        return (jnp.where(hit, at(j0_ref, j1_ref, k), jv),
                jnp.where(hit, at(d0_ref, d1_ref, k), dv))

    jv, dv = jax.lax.fori_loop(lo + 1, hi_ref[t] - base + 1, body,
                               (jv, dv))
    mask = eid < total_ref[0]
    ge_ref[...] = jnp.where(mask, eid + dv, 0)
    slot_ref[...] = jv
    msk_ref[...] = mask.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_ids", "tile_edges"))
def corank_map(start_e: jax.Array, row_start: jax.Array,
               total_edges: jax.Array, n_ids: int, tile_edges: int):
    """Map the contiguous edge ids ``[0, n)`` to ``(graph_e, slot,
    mask)``, ``n`` = ``n_ids`` rounded up to a whole number (>= 1) of
    ``tile_edges`` tiles; ids at or past ``total_edges`` are masked
    (``graph_e = 0``).  ``slot`` is ``searchsorted(start_e, id,
    "right") - 1`` for every unmasked id — an index into the caller's
    ``[H]`` arrays.  The shared core of :func:`edge_lb_map` and
    ``merge_path.merge_path_map``."""
    # a TPU compile also needs whole (8, 128) output tiles: a multiple
    # of 1024 edges (BalancerConfig.lb_tile_edges defaults to 2048)
    assert tile_edges % _LANES == 0, tile_edges
    h = start_e.shape[0]
    total = jnp.asarray(total_edges, jnp.int32)
    start_e = start_e.astype(jnp.int32)
    # drop slots that own no edge below `total`: kept starts are then
    # strictly increasing, so a tile's window holds <= tile_edges slots
    nxt = jnp.concatenate([start_e[1:], jnp.full((1,), _NO_START)])
    keep = (start_e < nxt) & (start_e < total)
    # two window blocks must exist past any slot: pad to whole blocks + 1
    hp = (-(-h // tile_edges) + 1) * tile_edges
    idx = compact(keep, hp)
    real = idx < h
    safe = jnp.where(real, idx, 0)
    cstart = jnp.where(real, start_e[safe], _NO_START)
    cdelta = jnp.where(real, row_start[safe] - start_e[safe], 0)
    cslot = jnp.where(real, idx, 0).astype(jnp.int32)

    grid = max(1, -(-n_ids // tile_edges))
    first = jnp.arange(grid, dtype=jnp.int32) * tile_edges
    last = jnp.minimum(first + tile_edges - 1, total - 1)
    lo = jnp.maximum(jnp.searchsorted(cstart, first, side="right") - 1, 0)
    hi = jnp.maximum(jnp.searchsorted(cstart, last, side="right") - 1, lo)

    tile_r = tile_edges // _LANES

    def window(offset):
        return pl.BlockSpec((tile_edges,),
                            lambda t, lo, hi, total: (lo[t] // tile_edges
                                                      + offset,),
                            memory_space=pltpu.SMEM)

    out = pl.BlockSpec((tile_r, _LANES), lambda t, lo, hi, total: (t, 0))
    shape = jax.ShapeDtypeStruct((grid * tile_r, _LANES), jnp.int32)
    ge, slot, msk = pallas_call(
        functools.partial(_kernel, tile_r=tile_r),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(grid,),
            in_specs=[window(0), window(1)] * 3,
            out_specs=[out] * 3),
        out_shape=[shape] * 3,
    )(lo.astype(jnp.int32), hi.astype(jnp.int32), total.reshape(1),
      cstart, cstart, cdelta, cdelta, cslot, cslot)
    return ge.reshape(-1), slot.reshape(-1), msk.reshape(-1).astype(bool)


@functools.partial(
    jax.jit,
    static_argnames=("n_enum", "tile_edges", "distribution", "num_tiles"))
def edge_lb_map(start_e: jax.Array, row_start: jax.Array, hval: jax.Array,
                total_edges: jax.Array, n_enum: int | None = None, *,
                tile_edges: int = 2048, distribution: str = "cyclic",
                num_tiles: int = 64):
    """Run the LB mapping kernel over ``n_enum`` edge ids.

    Returns (graph_e, slot_j, src_val, mask) flat arrays of length
    ``ceil(w_per * num_tiles / tile_edges) * tile_edges`` where
    ``w_per = ceil(n_enum / num_tiles)`` — the enumeration span padded
    to the kernel tile size.
    """
    if n_enum is None:
        n_enum = start_e.shape[0]  # caller really should pass the span
    w_per = -(-n_enum // num_tiles)
    span = w_per * num_tiles          # exact bijection domain
    total = jnp.minimum(jnp.asarray(total_edges, jnp.int32), span)
    ge, j, msk = corank_map(start_e, row_start, total, span, tile_edges)
    if distribution == "blocked":
        eid0 = jnp.arange(ge.shape[0], dtype=jnp.int32)
        inside = eid0 < span
        eid = jnp.where(inside,
                        (eid0 % num_tiles) * w_per + eid0 // num_tiles, 0)
        msk = msk[eid] & inside
        ge, j = jnp.where(msk, ge[eid], 0), j[eid]
    return ge, j, hval[j], msk
