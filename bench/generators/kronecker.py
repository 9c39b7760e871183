"""Graph500 Kronecker generator (the spec's reference ``kronecker_generator``).

For ``scale`` and ``edgefactor`` it draws M = edgefactor * 2^scale edges,
one bit level at a time with the initiator probabilities (A, B, C, D),
then permutes the vertex labels and the edge order, as the Graph500
specification does.  The result is an undirected multigraph edge list:
self-loops and duplicates are left in, for the consumer to drop.
"""
from __future__ import annotations

import numpy as np


def edges(params: dict, seed: int) -> tuple[np.ndarray, int]:
    """(M, 2) int64 edge list and the vertex count, from ``params``:
    ``scale``, ``edgefactor``, ``A``, ``B``, ``C`` (D = 1 - A - B - C)."""
    scale, edgefactor = int(params["scale"]), int(params["edgefactor"])
    a, b, c = float(params["A"]), float(params["B"]), float(params["C"])
    n = 1 << scale
    m = edgefactor * n
    rng = np.random.default_rng(seed)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ij = np.zeros((2, m), np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij[0] += ii.astype(np.int64) << bit
        ij[1] += jj.astype(np.int64) << bit
    ij = rng.permutation(n)[ij]
    ij = ij[:, rng.permutation(m)]
    return np.ascontiguousarray(ij.T), n
