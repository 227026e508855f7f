"""What a traversal has to do, whatever implements it, counted from the
reference's reached set: the edges it scans (the numerator of ``teps``)
and the least bytes it moves (the numerator of ``edge_pass_roofline``).
"""
from __future__ import annotations

import numpy as np

from bench.reference import WEIGHTED_APPS

# int32 entries
_WORD = 4


def scanned_edges(out_degree: np.ndarray, reached: np.ndarray) -> int:
    """The out-edges of every reached vertex: each is scanned once."""
    return int(out_degree[reached].sum(dtype=np.int64))


def least_bytes(app: str, out_degree: np.ndarray, reached: np.ndarray) -> int:
    """Per reached vertex its ``row_ptr`` pair and one label read; per
    scanned edge its ``col_idx`` entry, its weight where the app reads
    one, and its destination's label read and written."""
    vertices = int(np.count_nonzero(reached))
    per_edge = _WORD * (1 + (app in WEIGHTED_APPS) + 2)
    return (3 * _WORD * vertices
            + per_edge * scanned_edges(out_degree, reached))
