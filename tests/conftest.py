"""Fixtures shared by the test files."""
import jax
import numpy as np
import pytest

from repro.core.graph import from_edge_list
from repro.core.programs import program


def clear_programs():
    """Forget every built program: jit's in-memory caches and the
    program cache (``repro.core.programs``), so the next ``run_adaptive``
    call traces and lowers all of its programs."""
    jax.clear_caches()
    program.cache_clear()


@pytest.fixture
def cold_programs():
    """A test that starts with no program built."""
    clear_programs()


def kronecker_graph(scale=10, edgefactor=16, seed=3):
    """A small Graph500 Kronecker graph (initiator 0.57/0.19/0.19,
    labels permuted)."""
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, edgefactor << scale
    ij = np.zeros((2, m), np.int64)
    for bit in range(scale):
        ii = rng.random(m) > 0.76
        jj = rng.random(m) > np.where(ii, 0.19 / 0.24, 0.57 / 0.76)
        ij[0] += ii.astype(np.int64) << bit
        ij[1] += jj.astype(np.int64) << bit
    return from_edge_list(rng.permutation(n)[ij].T, n)
