"""Pallas TPU kernel: position-in-expert computation for MoE dispatch.

This is the ALB prefix machinery applied to token routing (DESIGN.md
section 5): given the flat expert assignment of T*K token-slots, each
slot needs its arrival rank within its expert — exactly the exclusive
prefix sum the graph LB executor builds over vertex degrees.

TPU mapping: the grid walks token tiles SEQUENTIALLY (TPU grid steps
execute in order), carrying per-expert running counters in a VMEM
accumulator — a tile-parallel scan with an O(E) carry, instead of the
O(T*K x E) one-hot cumsum matrix the pure-jnp oracle materializes
(moe._positions_in_expert).  Output: pos[i] = #earlier slots routed to
the same expert.

Known gap: Mosaic has no lowering for the in-kernel ``cumsum``, so the
TPU compile of this kernel fails.  ``repro.kernels.pallas_call`` never
falls back to the interpreter on a TPU, so that error surfaces as is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pallas_call


def _kernel(eid_ref, pos_ref, counts_ref, *, num_experts, tile):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    eid = eid_ref[0, :]                          # [tile]
    onehot = (eid[:, None] ==
              jax.lax.broadcasted_iota(jnp.int32, (tile, num_experts), 1))
    onehot = onehot.astype(jnp.int32)
    # rank within tile (exclusive) + carried per-expert base
    excl = jnp.cumsum(onehot, axis=0) - onehot   # [tile, E]
    base = counts_ref[0, :]                      # [E]
    pos = jnp.sum((excl + base[None, :]) * onehot, axis=1)
    pos_ref[0, :] = pos
    counts_ref[0, :] = base + jnp.sum(onehot, axis=0)


@functools.partial(jax.jit,
                   static_argnames=("num_experts", "tile"))
def positions_in_expert_kernel(flat_expert, num_experts: int,
                               tile: int = 1024):
    """flat_expert: [N] int32 -> pos: [N] int32 (arrival rank within
    expert)."""
    n = flat_expert.shape[0]
    np_ = -(-n // tile) * tile
    pad = np_ - n
    e = flat_expert
    if pad:
        e = jnp.pad(e, (0, pad), constant_values=num_experts + 1)
    grid = np_ // tile
    kern = functools.partial(_kernel, num_experts=num_experts, tile=tile)
    pos, _ = pallas_call(
        kern,
        grid=(grid,),
        in_specs=[pl.BlockSpec((1, tile), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, tile), lambda i: (0, i)),
                   pl.BlockSpec((1, num_experts), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, np_), jnp.int32),
                   jax.ShapeDtypeStruct((1, num_experts), jnp.int32)],
    )(e[None, :])
    return pos[0, :n]
