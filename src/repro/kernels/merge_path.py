"""Pallas TPU kernel for the merge-path executor (third backend).

Merge-path load balancing (Merrill & Garland's SpMV scheme, the
segmented-scan form of Gunrock-LB) removes the inspector entirely:
the frontier's whole edge range ``[0, total)`` is cut into equal-work
tiles of ``tile_edges`` edge ids each, and every tile locates its own
slice of the frontier by *co-ranked* search over the exclusive degree
prefix sum ``start_e`` — a diagonal search on the (vertices, edges)
merge matrix.  No degree bins, no huge-bin detection, no per-round
planning of any kind: the only data-dependent quantity is ``total``, a
device scalar, which is why the executor drops into the fused
device-resident traversal loop (DESIGN.md section 11) with zero host
involvement.

The tile mapping is the co-ranked windowed search of ``edge_lb.py``
(:func:`repro.kernels.edge_lb.corank_map`): per tile, the two scalar
co-ranks of its first and last edge id bound a window of at most
``tile_edges`` slots (zero-degree frontier members are dropped first),
and the kernel resolves every lane against that window only.  Here it
runs over the frontier's WHOLE edge range rather than the huge bin.
The tile emits (graph_edge, slot) pairs; the irregular gathers and the
scatter-combine stay in the XLA epilogue (``ops.merge_path_apply*``),
exactly like the other two backends.

Enumeration contract: ids are dealt contiguously (tile t owns
``[t * tile_edges, (t+1) * tile_edges)``), so per-tile edge loads
differ by at most one partial tail tile — the ``distribution`` knob of
the other backends does not apply.  Ids at or past ``total`` are
masked before any memory traffic.  Validated against a numpy
searchsorted oracle (tests/test_fused.py).
"""
from __future__ import annotations

import functools

import jax

from .edge_lb import corank_map


@functools.partial(jax.jit, static_argnames=("ecap", "tile_edges"))
def merge_path_map(start_e: jax.Array, row_start: jax.Array,
                   total_edges: jax.Array, ecap: int, *,
                   tile_edges: int = 2048):
    """Run the merge-path mapping kernel over ``ecap`` edge ids.

    ``start_e`` / ``row_start`` are the ``[H]`` exclusive degree prefix
    sum and CSR row starts of the frontier members; ``total_edges`` is
    the live edge count (device scalar, ids past it are masked).
    Returns ``(graph_e, slot_j, mask)`` flat arrays of length
    ``ceil(ecap / tile_edges) * tile_edges`` (at least one tile) — each
    kernel grid step is one equal-work tile of ``tile_edges``
    consecutive edge ids.
    """
    return corank_map(start_e, row_start, total_edges, ecap, tile_edges)
