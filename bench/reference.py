"""The plain reference: BFS levels and SSSP distances from
``scipy.sparse.csgraph`` over a host copy of the benchmark's CSR.  It
shares no code with the engine; labels of unreached vertices are
``csr.UNREACHED``."""
from __future__ import annotations

import numpy as np

from bench.csr import UNREACHED

# apps whose labels are path lengths over the edge weights
WEIGHTED_APPS = ("sssp",)


class Reference:
    def __init__(self, row_ptr: np.ndarray, col_idx: np.ndarray,
                 edge_w: np.ndarray):
        import scipy.sparse
        v = len(row_ptr) - 1
        e = int(row_ptr[-1])
        self.adj = scipy.sparse.csr_matrix(
            (edge_w[:e].astype(np.float64), col_idx[:e], row_ptr),
            shape=(v, v))
        self.memo = {}

    def labels(self, app: str, root: int) -> np.ndarray:
        from scipy.sparse import csgraph
        if app not in ("bfs",) + WEIGHTED_APPS:
            raise ValueError(f"no reference for app {app!r}")
        key = (app, root)
        if key not in self.memo:
            d = csgraph.shortest_path(self.adj, indices=root,
                                      unweighted=app not in WEIGHTED_APPS)
            self.memo[key] = np.where(np.isinf(d), UNREACHED,
                                      d).astype(np.int32)
        return self.memo[key]
