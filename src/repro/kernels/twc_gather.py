"""Pallas TPU kernel for the vertex-binned (TWC-analog) path.

Each grid step processes a tile of frontier vertices from one degree
bin; the bin's uniform width ``W`` fixes the inner trip count, so it is
identical across the tile (the TPU analogue of the warp-uniform
execution TWC buys on GPUs).  Emits (graph_e, anchor, val, mask) tiles;
gather/scatter is applied outside by XLA (see edge_lb.py for the
rationale).

TPU layout: a bin's per-vertex scalars arrive as ``[1, n]`` rows, so a
tile of vertices is a lane-aligned ``(1, 128k)`` block, and the kernel
writes ``[W, tile]`` blocks (edge offset on sublanes, vertex on lanes)
where every vertex's scalars broadcast down its own lane.  The wrapper
transposes the result back to the ``[n, W]`` contract.

``chunk`` — the pass index for unbounded bins (each pass covers edges
[chunk*W, (chunk+1)*W) of every vertex) — is a *runtime scalar operand*
(a scalar-prefetch argument), not a compile-time constant: the fully-jit
SPMD round (balancer.relax_spmd) iterates chunks with a
``lax.while_loop`` whose trip count is data-dependent, so the kernel
must accept a traced chunk.  The host-driven round passes Python ints,
which trace to the same single compiled kernel.

Batched queries (DESIGN.md section 7): the (graph_e, anchor, mask)
tiles depend only on the union frontier's bin members, so
``ops.twc_bin_apply*`` launch this kernel ONCE per round for the whole
batch and re-gather per-query values/activity from the ``[B, V]``
arrays in the XLA epilogue (the ``val`` output carries a single
query's view and is ignored there).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_call

_LANES = 128
_BLOCK_ELEMS = 1 << 17        # int32 elements per output block (512 KiB)
ROW_PAD = 8                   # returned rows: the bin size rounded up to this


def _kernel(chunk_ref, vidx_ref, deg_ref, row_ref, val_ref,
            ge_ref, anchor_ref, val_out_ref, msk_ref,
            *, width: int, sentinel: int):
    # transposed tile: edge offsets on sublanes, bin members on lanes,
    # so each member's scalars broadcast down its own lane column
    vid = vidx_ref[...]                         # [1, tile]
    off = (chunk_ref[0] * width
           + jax.lax.broadcasted_iota(jnp.int32, ge_ref.shape, 0))
    emask = (off < deg_ref[...]) & (vid < sentinel)
    ge_ref[...] = jnp.where(emask, row_ref[...] + off, 0)
    anchor_ref[...] = jnp.broadcast_to(vid, emask.shape)
    val_out_ref[...] = jnp.broadcast_to(val_ref[...], emask.shape)
    msk_ref[...] = emask.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("width", "sentinel"))
def twc_bin_map(vidx: jax.Array, deg: jax.Array, row_start: jax.Array,
                val: jax.Array, *, width: int,
                chunk: jax.Array | int = 0, sentinel: int = 1 << 30):
    """Expand one degree bin into (graph_e, anchor, val, mask) tiles.

    Returns four ``[bp, width]`` arrays, ``bp`` = the bin size rounded
    up to ``ROW_PAD``; padded rows carry ``anchor = sentinel`` and an
    all-False mask.  The kernel works on the transposed ``[width,
    tile]`` layout (``tile`` = a multiple of 128 members, sized so an
    output block stays near 512 KiB) and the result is transposed back
    here."""
    b = vidx.shape[0]
    bp = -(-b // ROW_PAD) * ROW_PAD
    tile = max(_LANES, min(_BLOCK_ELEMS // width, 16 * _LANES)
               // _LANES * _LANES)
    bk = -(-bp // tile) * tile
    pad = bk - b
    if pad:
        vidx = jnp.pad(vidx, (0, pad), constant_values=sentinel)
        deg = jnp.pad(deg, (0, pad))
        row_start = jnp.pad(row_start, (0, pad))
        val = jnp.pad(val, (0, pad))
    kern = functools.partial(_kernel, width=width, sentinel=sentinel)
    vec = pl.BlockSpec((1, tile), lambda i, chunk: (0, i))
    out = pl.BlockSpec((width, tile), lambda i, chunk: (0, i))
    outs = pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bk // tile,),
            in_specs=[vec] * 4, out_specs=[out] * 4),
        out_shape=[jax.ShapeDtypeStruct((width, bk), jnp.int32),
                   jax.ShapeDtypeStruct((width, bk), jnp.int32),
                   jax.ShapeDtypeStruct((width, bk), val.dtype),
                   jax.ShapeDtypeStruct((width, bk), jnp.int32)],
    )(jnp.asarray(chunk, jnp.int32).reshape(1), vidx[None, :],
      deg[None, :], row_start[None, :], val[None, :])
    ge, anchor, v, msk = (o.T[:bp] for o in outs)
    return ge, anchor, v, msk.astype(bool)
