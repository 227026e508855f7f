"""Traversal roots drawn by ``rng``, uniformly within each of
``strata`` equal-count strata of the eligible vertices ranked by
out-degree, so that each root is uniform over the eligible vertices and
every cycle holds cheap and dear traversals alike.  The cycle visits
them lowest, highest, second lowest, second highest, and so on.

Parameters (the traffic file's ``roots`` object):

- ``eligible``: ``"out_degree_ge_1"``, the Graph500 kernel-2 rule;
- ``strata``: how many roots the cycle holds.
"""
from __future__ import annotations

import numpy as np


def sample(spec: dict, *, out_degree: np.ndarray,
           rng: np.random.Generator) -> list:
    if spec["eligible"] != "out_degree_ge_1":
        raise ValueError(f"unknown eligibility rule {spec['eligible']!r}")
    eligible = np.flatnonzero(out_degree > 0)
    ranked = eligible[np.argsort(out_degree[eligible], kind="stable")]
    strata = np.array_split(ranked, spec["strata"])
    order, lo, hi = [], 0, len(strata) - 1
    while lo <= hi:
        order.append(lo)
        if hi != lo:
            order.append(hi)
        lo, hi = lo + 1, hi - 1
    return [int(strata[k][rng.integers(len(strata[k]))]) for k in order]
