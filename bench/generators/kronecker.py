"""Graph500 Kronecker (R-MAT) graph, generated on the device.

``2**scale`` vertices and ``edge_factor * 2**scale`` directed edges;
each edge picks one quadrant of the adjacency matrix per bit of its
endpoints, with probabilities ``a``, ``b``, ``c`` and ``1-a-b-c``
(quadrant 0 keeps both bits 0, 1 sets the destination bit, 2 the
source bit, 3 both).  Weights are uniform in ``1..max_weight``.
Parallel edges collapse to their least weight, as
``repro.core.graph.rmat`` does.

One graph is drawn from ``params["graph_seed"]``; a run's seed relabels
its vertices by a random permutation (as Graph500's generator does), so
every seed has an isomorphic graph, with the same edge count.  Drawn
anew for each seed, the graph's frontiers fall on either side of a
power-of-two bucket, and a seed's rate moved by 10% on a TPU v5e.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import csr


def _threshold(p: float) -> int:
    return min(int(round(p * 2 ** 32)), 2 ** 32 - 1)


@partial(jax.jit, static_argnames=("scale", "edge_factor", "a", "b", "c",
                                   "max_weight"))
def _edges(key, *, scale, edge_factor, a, b, c, max_weight):
    m = edge_factor << scale
    kq, kw = jax.random.split(key)
    ta, tab, tabc = (jnp.uint32(_threshold(p)) for p in (a, a + b, a + b + c))

    def bit(i, carry):
        src, dst = carry
        r = jax.random.bits(jax.random.fold_in(kq, i), (m,), jnp.uint32)
        quad = ((r >= ta).astype(jnp.int32) + (r >= tab).astype(jnp.int32)
                + (r >= tabc).astype(jnp.int32))
        return (src << 1) | (quad >> 1), (dst << 1) | (quad & 1)

    zeros = jnp.zeros((m,), jnp.int32)
    src, dst = lax.fori_loop(0, scale, bit, (zeros, zeros))
    w = jax.random.randint(kw, (m,), 1, max_weight + 1, jnp.int32)
    return src, dst, w


@partial(jax.jit, static_argnames=("n",))
def _permutation(key, n):
    return jax.random.permutation(key, n).astype(jnp.int32)


def _static(params: dict) -> dict:
    return {k: params[k] for k in ("scale", "edge_factor", "a", "b", "c",
                                   "max_weight")}


@partial(jax.jit, static_argnames=("scale", "edge_factor", "a", "b", "c",
                                   "max_weight"))
def _relabelled_edges(key, perm_key, **kw):
    src, dst, w = _edges(key, **kw)
    perm = _permutation(perm_key, 1 << kw["scale"])
    return perm[src], perm[dst], w


def edge_list(params: dict, seed: int):
    """The run's raw ``(src, dst, weight)`` int32 edges, relabelled,
    before the collapse."""
    return _relabelled_edges(csr.seed_key(params["graph_seed"]),
                             csr.seed_key(seed), **_static(params))


@partial(jax.jit, static_argnames=("scale", "edge_factor", "a", "b", "c",
                                   "max_weight"))
def _generate(key, perm_key, **kw):
    src, dst, w = _relabelled_edges(key, perm_key, **kw)
    return csr.from_edges(src, dst, w, 1 << kw["scale"])


def generate(params: dict, seed: int):
    """``(row_ptr, col_idx, edge_w)`` on the default device, made in one
    jitted call and cut to the graph's edge count."""
    return csr.trim(*_generate(csr.seed_key(params["graph_seed"]),
                               csr.seed_key(seed), **_static(params)))



def relabelling(params: dict, seed: int) -> np.ndarray:
    """The run's id of each vertex of the drawn graph."""
    return np.asarray(_permutation(csr.seed_key(seed), 1 << params["scale"]))
