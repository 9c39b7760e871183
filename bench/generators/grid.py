"""Square lattice: the stand-in for a road network (planar, degree at most
4, unweighted, connected, hop diameter 2 (side - 1)).

Vertex ``r * side + c`` sits at row ``r`` and column ``c`` and is joined
to its right and lower neighbours, so labels run row by row and
neighbours lie at most ``side`` labels apart, as in a road-network file
numbered in a spatial order.  The lattice is fixed by ``side``; the seed
is not used.
"""
from __future__ import annotations

import numpy as np


def edges(params: dict, seed: int) -> tuple[np.ndarray, int]:
    """(M, 2) int64 undirected edge list, each edge once, and the vertex
    count, from ``params["side"]``."""
    del seed
    side = int(params["side"])
    n = side * side
    v = np.arange(n, dtype=np.int64)
    right = v[v % side != side - 1]
    down = v[v < n - side]
    return np.concatenate([np.stack([right, right + 1], axis=1),
                           np.stack([down, down + side], axis=1)]), n
