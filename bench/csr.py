"""CSR assembly on the device, shared by the graph generators.

The result is the CSR that ``repro.core.graph.from_edge_list`` builds
from the same edge list: rows in source order, each row's edges in
ascending destination order, parallel edges collapsed to the one of
least weight.  Inside ``jit`` the edge arrays keep the raw edge count:
entries past ``row_ptr[-1]`` belong to no vertex (destination ``V - 1``,
weight ``UNREACHED``) until :func:`trim` cuts them off.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# the engine's label for an unreached vertex, and the weight of a
# padding edge (also 2**30 in repro.core.graph.INF)
UNREACHED = 1 << 30


def from_edges(src, dst, w, num_vertices: int):
    """``(row_ptr[V+1], col_idx[M], edge_w[M])`` from int32 ``[M]``
    edge arrays.  An edge whose ``src`` is ``num_vertices`` or more is
    dropped; duplicates keep their least weight.  Call inside ``jit``."""
    v = num_vertices
    # (src, dst, weight) order puts each duplicate run's least weight
    # first, so keeping the head of every run is the min-collapse
    src, dst, w = lax.sort((src, dst, w), num_keys=3)
    dup = jnp.concatenate([jnp.zeros((1,), bool),
                           (src[1:] == src[:-1]) & (dst[1:] == dst[:-1])])
    key = jnp.where(dup | (src >= v), v, src)
    # the kept edges are already in order: a stable sort on the key
    # moves the dropped ones behind them
    key, dst, w = lax.sort((key, dst, w), num_keys=1, is_stable=True)
    live = key < v
    row_ptr = jnp.searchsorted(key, jnp.arange(v + 1, dtype=jnp.int32),
                               side="left", method="scan")
    return (row_ptr.astype(jnp.int32),
            jnp.where(live, dst, v - 1).astype(jnp.int32),
            jnp.where(live, w, UNREACHED).astype(jnp.int32))


def trim(row_ptr, col_idx, edge_w):
    """The CSR with the entries past ``row_ptr[-1]`` cut off, so that
    the engine's edge count is the graph's."""
    e = int(row_ptr[-1])
    return row_ptr, col_idx[:e], edge_w[:e]


def seed_key(seed: int):
    """A PRNG key from a seed of any size (``jax.random.key`` keeps only
    the low 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
