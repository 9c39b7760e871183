"""The benchmark's copied graph generator and the reference's graph."""
from __future__ import annotations

import numpy as np

from bench import reference
from bench.generators import kronecker

KRON = {"scale": 12, "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19}


def test_kronecker_edge_count_and_range():
    edges, n = kronecker.edges(KRON, 1)
    assert n == 1 << 12
    assert edges.shape == (16 << 12, 2)
    assert edges.min() >= 0 and edges.max() < n


def test_kronecker_is_deterministic_from_its_seed():
    a, _ = kronecker.edges(KRON, 3)
    b, _ = kronecker.edges(KRON, 3)
    c, _ = kronecker.edges(KRON, 4)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)


def test_kronecker_initiator_skews_degrees():
    edges, n = kronecker.edges(KRON, 1)
    deg = np.bincount(edges.ravel(), minlength=n)
    # A = 0.57 concentrates edges: the top vertex holds far more than the
    # mean, and many vertices hold none
    assert deg.max() > 20 * deg.mean()
    assert (deg == 0).sum() > n // 10


def test_kronecker_labels_are_permuted():
    # unpermuted, a source lands in the lower half of the ids with
    # probability A + B = 0.76 at the top bit level; permuted, with 1/2
    edges, n = kronecker.edges(KRON, 1)
    low = (edges[:, 0] < n // 2).mean()
    assert abs(low - 0.5) < 0.05
    # and the highest-degree vertex is not pinned to id 0
    degs = [np.bincount(kronecker.edges(KRON, s)[0].ravel(),
                        minlength=n).argmax() for s in (1, 2, 3)]
    assert len(set(degs)) > 1


def test_reference_graph_matches_program_graph():
    from repro.core.graph import from_edge_list
    edges, n = kronecker.edges({**KRON, "scale": 10}, 5)
    rg = reference.build(edges, n)
    g = from_edge_list(edges, n)
    assert int(g.n_edges) == int(rg.deg.sum())
    assert np.array_equal(np.asarray(g.degree), rg.deg)
