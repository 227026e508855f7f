"""CSR graph container + synthetic graph generators.

The paper evaluates on RMAT power-law graphs, social networks (orkut,
twitter40), and road networks (road-USA).  We generate structurally
equivalent synthetic inputs: RMAT (power-law out-degree), a 2-D grid
"road" network (constant low degree, huge diameter), and a uniform
random graph (Erdos-Renyi-ish).

The device-resident representation is CSR (row_ptr, col_idx, edge_w),
exactly as in the paper (Section 4.1: "like most systems in this space,
our system uses a CSR representation of the graph to save space").
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# Sentinel "infinity" for int32 distance labels.  We avoid INT32_MAX so
# that INF + weight does not wrap around.
INF = np.int32(1 << 30)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Graph:
    """Device-resident CSR graph.

    row_ptr : int32[V+1]   prefix of out-degrees
    col_idx : int32[E]     destination vertex of each edge
    edge_w  : int32[E]     edge weights (all-ones for unweighted apps)
    """

    row_ptr: jax.Array
    col_idx: jax.Array
    edge_w: jax.Array

    # ---- pytree plumbing -------------------------------------------------
    def tree_flatten(self):
        return (self.row_ptr, self.col_idx, self.edge_w), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # ---- basic properties ------------------------------------------------
    @property
    def num_vertices(self) -> int:
        return self.row_ptr.shape[0] - 1

    @property
    def num_edges(self) -> int:
        return self.col_idx.shape[0]

    @property
    def version(self) -> int:
        """Monotonically increasing topology version (DESIGN.md
        section 10).  Every derived structure memoized on the Graph —
        the reverse CSR below, the pull enumerations in
        ``repro.core.balancer``, the host edge map in
        ``repro.core.streaming`` — keys its cache entry on this value,
        so a version bump (``bump_version``, issued by the streaming
        update path) atomically invalidates all of them.  Stored
        outside the pytree: a traced Graph never sees it and version
        bumps never change jit cache keys."""
        return self.__dict__.get("_version", 0)

    def bump_version(self) -> None:
        """Advance :attr:`version` after an in-place topology change
        (``repro.core.streaming.apply_updates(..., in_place=True)``).
        Must be called by ANY code that swaps this object's CSR arrays
        underneath existing references — the memoized ``reverse()`` /
        pull-enumeration caches check the version on every lookup, so
        the bump is what keeps them from serving the old topology."""
        object.__setattr__(self, "_version", self.version + 1)

    def out_degrees(self) -> jax.Array:
        return self.row_ptr[1:] - self.row_ptr[:-1]

    def max_out_degree(self) -> int:
        return int(jnp.max(self.out_degrees()))  # repro: allow[host-sync] -- one-time planner-setup scalar, not per-round

    def reverse(self) -> "Graph":
        """Memoized reverse view (:func:`reverse_graph`): the CSC of
        this graph stored as a CSR, i.e. in-edges become out-edges.

        Pull-direction rounds (DESIGN.md section 9) traverse it every
        round, so the host-side transpose is built once per Graph
        object and cached (the cache is an ordinary attribute, not a
        pytree leaf — a jit-traced Graph never sees it).

        The cache entry is keyed on :attr:`version`: an in-place
        topology change (DESIGN.md section 10) bumps the version, so a
        stale transpose can never be served — without the key, a pull
        round after a mutation would silently traverse the old
        topology."""
        cached = self.__dict__.get("_reverse_cache")
        if cached is None or cached[0] != self.version:
            cached = (self.version, reverse_graph(self))
            object.__setattr__(self, "_reverse_cache", cached)
        return cached[1]


# ---------------------------------------------------------------------------
# Construction helpers (host side, numpy).
# ---------------------------------------------------------------------------

def from_edge_list(src: np.ndarray, dst: np.ndarray, num_vertices: int,
                   weights: np.ndarray | None = None,
                   dedup: bool = True) -> Graph:
    """Build a CSR Graph from a COO edge list (host-side).

    ``dedup=True`` collapses parallel edges deterministically: each
    (src, dst) pair keeps the **minimum** weight among its duplicates
    (for unweighted input all duplicates are unit weight, so any
    representative is equivalent).  Min is the right collapse for the
    shortest-path family this repo propagates — a parallel edge bundle
    relaxes exactly like its cheapest member — and, unlike the previous
    keep-first-occurrence rule, does not depend on the input edge
    order.
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if dedup and len(src):
        key = src * np.int64(num_vertices) + dst
        if weights is None:
            key = np.unique(key)
        else:
            # each run of equal keys collapses to its minimum weight;
            # the order inside a run does not matter, so one unstable
            # sort of the keys replaces a (key, weight) lexsort
            order = np.argsort(key)
            key, weights = key[order], np.asarray(weights)[order]
            first = np.flatnonzero(
                np.concatenate([[True], key[1:] != key[:-1]]))
            key = key[first]
            weights = np.minimum.reduceat(weights, first)
        # the unique keys ascend, so the edges come out in the (src,
        # dst) order the CSR needs
        src, dst = np.divmod(key, np.int64(num_vertices))
    else:
        # one stable sort on the (src, dst) key: parallel edges keep
        # their input order, and already-sorted input sorts in O(E)
        order = np.argsort(src * np.int64(num_vertices) + dst,
                           kind="stable")
        src, dst = src[order], dst[order]
        if weights is not None:
            weights = np.asarray(weights)[order]
    if weights is None:
        weights = np.ones(len(src), dtype=np.int32)
    else:
        weights = np.asarray(weights, dtype=np.int32)
    counts = np.bincount(src, minlength=num_vertices).astype(np.int32)
    row_ptr = np.zeros(num_vertices + 1, dtype=np.int32)
    np.cumsum(counts, out=row_ptr[1:])
    return Graph(
        row_ptr=jnp.asarray(row_ptr),
        col_idx=jnp.asarray(dst.astype(np.int32)),
        edge_w=jnp.asarray(weights),
    )


def rmat(scale: int, edge_factor: int = 16, seed: int = 0,
         a: float = 0.57, b: float = 0.19, c: float = 0.19,
         weighted: bool = True, max_weight: int = 100) -> Graph:
    """RMAT generator (Chakrabarti et al.), the paper's power-law inputs.

    Produces ~2**scale vertices, edge_factor * 2**scale directed edges
    with a power-law out-degree distribution (a-heavy corner => vertex 0
    region accumulates huge out-degree, mirroring rmat23's 35M max Dout).
    """
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = edge_factor * n
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    ab, abc = a + b, a + b + c
    for bit in range(scale):
        r = rng.random(m)
        # pick quadrant 0=a 1=b 2=c 3=d: the number of cumulative
        # thresholds r reaches
        quad = (r >= a).astype(np.int8) + (r >= ab) + (r >= abc)
        src <<= 1
        src |= quad >= 2
        dst <<= 1
        dst |= quad & 1
    w = rng.integers(1, max_weight + 1, size=m) if weighted else None
    return from_edge_list(src, dst, n, weights=w)


def road_grid(side: int, seed: int = 0, weighted: bool = True,
              max_weight: int = 100) -> Graph:
    """2-D grid graph: constant degree <= 4, diameter 2*side.

    Structural stand-in for road-USA (max degree 9, diameter 6261).
    """
    rng = np.random.default_rng(seed)
    n = side * side
    vs = np.arange(n).reshape(side, side)
    srcs, dsts = [], []
    # bidirectional horizontal + vertical edges
    srcs += [vs[:, :-1].ravel(), vs[:, 1:].ravel(),
             vs[:-1, :].ravel(), vs[1:, :].ravel()]
    dsts += [vs[:, 1:].ravel(), vs[:, :-1].ravel(),
             vs[1:, :].ravel(), vs[:-1, :].ravel()]
    src = np.concatenate(srcs)
    dst = np.concatenate(dsts)
    w = rng.integers(1, max_weight + 1, size=len(src)) if weighted else None
    return from_edge_list(src, dst, n, weights=w)


def uniform_random(num_vertices: int, avg_degree: int = 8, seed: int = 0,
                   weighted: bool = True, max_weight: int = 100) -> Graph:
    """Uniform random digraph (no skew) — the balanced control input."""
    rng = np.random.default_rng(seed)
    m = num_vertices * avg_degree
    src = rng.integers(0, num_vertices, size=m)
    dst = rng.integers(0, num_vertices, size=m)
    w = rng.integers(1, max_weight + 1, size=m) if weighted else None
    return from_edge_list(src, dst, num_vertices, weights=w)


def to_coo(g: Graph) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side COO expansion ``(src, dst, weight)`` of a CSR graph.

    The one place the ``row_ptr``-to-source expansion lives; the
    partitioner (which slices edges by owner), ``reverse_graph`` and the
    benchmark symmetrizer all consume it.

    Only the ``row_ptr[-1]`` edges owned by some vertex are expanded:
    a padded graph (``pad_graph``, or the streaming shapes of
    DESIGN.md section 10) stores sentinel-targeting filler beyond that
    point, which belongs to no vertex and is not part of the semantic
    edge set.
    """
    row_ptr = np.asarray(g.row_ptr).astype(np.int64)
    e_real = int(row_ptr[-1])
    dst = np.asarray(g.col_idx)[:e_real].astype(np.int64)
    w = np.asarray(g.edge_w)[:e_real]
    src = np.repeat(np.arange(g.num_vertices, dtype=np.int64),
                    row_ptr[1:] - row_ptr[:-1])
    return src, dst, w


def reverse_graph(g: Graph) -> Graph:
    """CSC view (incoming edges) as a CSR graph — used by pull operators.

    Shape-preserving: when ``g`` carries edge padding (its ``col_idx``
    is longer than ``row_ptr[-1]``), the transpose is padded back to
    the same edge capacity with the same sentinel-targeting filler, so
    pull rounds over a streaming graph (DESIGN.md section 10) see
    fixed shapes across versions, exactly like push rounds over the
    forward CSR."""
    src, dst, w = to_coo(g)
    rg = from_edge_list(dst, src, g.num_vertices, weights=w,
                        dedup=False)
    ecap, e = g.num_edges, rg.num_edges
    if ecap > e:
        vp = g.num_vertices
        rg = Graph(
            row_ptr=rg.row_ptr,
            col_idx=jnp.concatenate(
                [rg.col_idx, jnp.full((ecap - e,), vp - 1, jnp.int32)]),
            edge_w=jnp.concatenate(
                [rg.edge_w, jnp.full((ecap - e,), INF, jnp.int32)]))
    if "_v_real" in g.__dict__:
        object.__setattr__(rg, "_v_real", g.__dict__["_v_real"])
    return rg


def symmetrized(g: Graph) -> Graph:
    """Undirected view: every edge plus its reverse (deduplicated) —
    what cc and kcore expect.

    Weights are preserved on both directions; when the input already
    has both (u, v) and (v, u) with different weights, dedup keeps the
    minimum, so ``w(u, v) == w(v, u)`` holds in the result and weighted
    SSSP over a symmetrized graph relaxes real edge weights (it used to
    silently degrade to unit weights / BFS)."""
    src, dst, w = to_coo(g)
    return from_edge_list(np.concatenate([src, dst]),
                          np.concatenate([dst, src]), g.num_vertices,
                          weights=np.concatenate([w, w]))


def highest_out_degree_vertex(g: Graph) -> int:
    """Paper's bfs/sssp source for power-law graphs."""
    return int(jnp.argmax(g.out_degrees()))  # repro: allow[host-sync] -- one-time benchmark-setup source pick


# ---------------------------------------------------------------------------
# Padding: devices want power-of-two-ish aligned arrays.
# ---------------------------------------------------------------------------

def pad_graph(g: Graph, v_multiple: int = 8, e_multiple: int = 1024) -> Graph:
    """Pad V and E to multiples so Pallas BlockSpecs tile cleanly.

    Padded vertices have degree 0.  Padded edges must target a *padded*
    vertex: the INF-ish weight only protects weight-respecting
    operators, and an executor that enumerates edge ids over the padded
    span would corrupt a real vertex's label under weight-ignoring
    operators (cc, kcore) if padding aimed at one.  So whenever edge
    padding exists, vertex padding is forced to exist too (``vp > v``)
    and every padded edge points at the padded vertex ``vp - 1`` —
    degree 0, label never read.
    """
    v, e = g.num_vertices, g.num_edges
    vp = -(-v // v_multiple) * v_multiple
    ep = -(-e // e_multiple) * e_multiple
    if ep > e and vp == v:
        vp = v + v_multiple           # guarantee a padded-edge target
    if vp == v and ep == e:
        return g
    row_ptr = jnp.concatenate(
        [g.row_ptr, jnp.full((vp - v,), g.row_ptr[-1], dtype=jnp.int32)])
    col_idx = jnp.concatenate(
        [g.col_idx, jnp.full((ep - e,), vp - 1, dtype=jnp.int32)])
    edge_w = jnp.concatenate(
        [g.edge_w, jnp.full((ep - e,), INF, dtype=jnp.int32)])
    return Graph(row_ptr=row_ptr, col_idx=col_idx, edge_w=edge_w)
