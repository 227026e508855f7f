"""The benchmark's device graph generator and root sampler, on the CPU
at small sizes: the CSR is bitwise what ``repro.core.graph`` builds from
the same edge list, the Kronecker draw follows its initiator, every seed
of a configuration has the same graph up to its vertices' names, and
the roots are drawn from the seed."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                if p not in sys.path]

from bench import csr  # noqa: E402
from bench.generators import kronecker  # noqa: E402
from bench.samplers import stratified  # noqa: E402
from repro.core import graph as G  # noqa: E402

KRON = dict(scale=10, edge_factor=16, a=0.57, b=0.19, c=0.19, max_weight=100,
            graph_seed=1)
SEEDS = [0, 12345, 2 ** 31 + 7, 2 ** 33 + 7]


def _arrays(csr_triple):
    return [np.asarray(a) for a in csr_triple]


@pytest.mark.parametrize("scale,seed", [(8, 0), (10, 12345),
                                        (12, 2 ** 31 + 7)])
def test_kronecker_csr_equals_from_edge_list(scale, seed):
    p = dict(KRON, scale=scale)
    row_ptr, col_idx, edge_w = _arrays(kronecker.generate(p, seed))
    src, dst, w = _arrays(kronecker.edge_list(p, seed))
    want = G.from_edge_list(src, dst, 1 << scale, weights=w)
    # no padding: the engine's edge count is the graph's
    assert len(col_idx) == len(edge_w) == want.num_edges < len(src)
    np.testing.assert_array_equal(row_ptr, np.asarray(want.row_ptr))
    np.testing.assert_array_equal(col_idx, np.asarray(want.col_idx))
    np.testing.assert_array_equal(edge_w, np.asarray(want.edge_w))


def test_kronecker_quadrant_frequencies():
    """Each bit of an edge's endpoints picks quadrant (src bit, dst bit)
    with probabilities a, b, c, d: the counts lie within 5 standard
    deviations of a binomial draw (read before the relabelling)."""
    p = dict(KRON, scale=12)
    src, dst, _ = _arrays(kronecker._edges(
        kronecker.csr.seed_key(3), **kronecker._static(p)))
    n = 0
    counts = np.zeros(4)
    for bit in range(p["scale"]):
        q = 2 * ((src >> bit) & 1) + ((dst >> bit) & 1)
        counts += np.bincount(q, minlength=4)
        n += len(q)
    probs = np.array([p["a"], p["b"], p["c"], 1 - p["a"] - p["b"] - p["c"]])
    sd = np.sqrt(n * probs * (1 - probs))
    assert (np.abs(counts - n * probs) < 5 * sd).all(), counts / n


def _relabelling(seed):
    """The run's id of each vertex of the drawn graph."""
    return np.asarray(kronecker._permutation(csr.seed_key(seed),
                                             1 << KRON["scale"]))


def test_kronecker_seeds_relabel_one_graph():
    """Every seed's graph is the configuration's graph with its vertices
    renamed by the seed's permutation."""
    base = G.to_coo(G.Graph(*kronecker.generate(KRON, 0)))
    inv0 = np.argsort(_relabelling(0))
    want = set(zip(inv0[base[0]], inv0[base[1]], base[2]))
    for seed in SEEDS[1:]:
        perm = _relabelling(seed)
        assert sorted(perm) == list(range(1 << KRON["scale"]))
        src, dst, w = G.to_coo(G.Graph(*kronecker.generate(KRON, seed)))
        inv = np.argsort(perm)
        assert set(zip(inv[src], inv[dst], w)) == want


@pytest.mark.parametrize("scale", [8, 10])
def test_same_seed_same_graph_other_seed_same_shapes(scale):
    params = dict(KRON, scale=scale)
    a = _arrays(kronecker.generate(params, SEEDS[2]))
    b = _arrays(kronecker.generate(params, SEEDS[2]))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    for seed in SEEDS:
        c = _arrays(kronecker.generate(params, seed))
        assert [x.shape for x in c] == [x.shape for x in a]
    # seeds 2**31 + 7 and 2**33 + 7 share their low 32 bits
    c2, c3 = (np.asarray(kronecker.generate(params, s)[1])
              for s in SEEDS[2:])
    assert not np.array_equal(c2, c3)


def test_stratified_sampler():
    deg = np.array([0, 1, 5, 2, 0, 9, 3, 7, 4, 8])
    spec = dict(eligible="out_degree_ge_1", strata=3)
    roots = [stratified.sample(spec, out_degree=deg,
                               rng=np.random.default_rng(s))
             for s in range(50)]
    for cycle in roots:
        assert len(cycle) == 3 and all(deg[v] > 0 for v in cycle)
        # ranked degrees 1 2 3 | 4 5 7 | 8 9: lowest, highest, middle
        assert deg[cycle[0]] <= 3 and deg[cycle[1]] >= 8
        assert 4 <= deg[cycle[2]] <= 7
    assert len({tuple(c) for c in roots}) > 10
    again = stratified.sample(spec, out_degree=deg,
                              rng=np.random.default_rng(7))
    assert again == roots[7]


@pytest.mark.parametrize("strata", [1, 3, 5])
def test_stratified_roots_are_uniform_over_eligible_vertices(strata):
    """Equal-count strata, one root drawn uniformly in each: over many
    seeds every eligible vertex is drawn about equally often and no
    other vertex ever is."""
    deg = np.random.default_rng(0).integers(0, 6, 60)
    spec = dict(eligible="out_degree_ge_1", strata=strata)
    counts = np.zeros(len(deg))
    draws = 4000
    for s in range(draws):
        for v in stratified.sample(spec, out_degree=deg,
                                   rng=np.random.default_rng(s)):
            counts[v] += 1
    eligible = deg > 0
    assert counts[~eligible].sum() == 0
    # each eligible vertex: binomial with p = strata / eligible
    p = strata / eligible.sum()
    sd = np.sqrt(draws * p * (1 - p))
    assert (np.abs(counts[eligible] - draws * p) < 5 * sd).all()


def test_stratified_sampler_refuses_an_unknown_rule():
    with pytest.raises(ValueError, match="eligibility"):
        stratified.sample(dict(eligible="all", strata=2),
                          out_degree=np.ones(4, int),
                          rng=np.random.default_rng(0))


@pytest.mark.parametrize("num_vertices", [1, 7, 64])
def test_csr_trim_drops_dropped_edges(num_vertices):
    """Edges whose source is V or more, and duplicates past the least
    weight, sit past ``row_ptr[-1]`` and are cut off by ``trim``."""
    rng = np.random.default_rng(num_vertices)
    m = 4 * num_vertices + 3
    src = rng.integers(0, num_vertices + 2, m).astype(np.int32)
    dst = rng.integers(0, num_vertices, m).astype(np.int32)
    w = rng.integers(1, 9, m).astype(np.int32)
    row_ptr, col_idx, edge_w = _arrays(csr.trim(*csr.from_edges(
        src, dst, w, num_vertices)))
    keep = src < num_vertices
    want = G.from_edge_list(src[keep], dst[keep], num_vertices,
                            weights=w[keep])
    np.testing.assert_array_equal(row_ptr, np.asarray(want.row_ptr))
    np.testing.assert_array_equal(col_idx, np.asarray(want.col_idx))
    np.testing.assert_array_equal(edge_w, np.asarray(want.edge_w))
