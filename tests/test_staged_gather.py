"""The staged dist/sigma gather (the Mosaic-compilable node-blocked
formulation): per-(dst-block, src-block) layout integrity, and 3-way
bit-for-bit parity of the staged kernel vs a LEGACY direct-gather
kernel vs the XLA reference.

The legacy kernel below is the pre-staging formulation — it indexes the
``pltpu.ANY`` dist/sigma refs directly per edge, which only interpret
mode can execute (Mosaic rejects it; ``tools/check_kernels.py`` bans it
from ``src/repro/kernels``).  Running it here against the SAME
pair-bucketed layout (it simply ignores ``block_sb``) pins down that
the staged path changed only the data movement, not one bit of the
result.  Sigma values come from real BFS runs (exact small-integer
floats), so every parity check is assert_array_equal.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import (build_csc_layout, grid_graph, partition_graph,
                        rmat_graph)
from repro.core.bfs import bfs_sssp_batched
from repro.core.partition import shard_vertex_range
from repro.kernels.frontier import (frontier_block_bitmap,
                                    frontier_expand_batched_ref,
                                    frontier_expand_node_blocked_pallas,
                                    frontier_expand_sharded_ref,
                                    pallas_supported)


def _bfs_state(g, batch, seed=0):
    rng = np.random.default_rng(seed)
    sources = jnp.asarray(rng.integers(0, g.n_nodes, batch), jnp.int32)
    res = bfs_sssp_batched(g, sources)
    levels = jnp.asarray(rng.integers(0, 4, batch), jnp.int32)
    return res.dist, res.sigma, levels


# ---------------------------------------------------------------------------
# Layout integrity: per-(dst block, src block) edge ranges
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make,block_v,block_e", [
    (lambda: rmat_graph(9, 8, seed=5), 64, 128),
    (lambda: grid_graph(48, 24), 100, 256),
    (lambda: rmat_graph(10, 4, seed=2), 37, 128),
])
def test_pair_bucketed_layout_integrity(make, block_v, block_e):
    """The staged gather's structural contract: edge blocks are pure in
    BOTH the destination block and the source block, the (nb, sb) pair
    sequence is lexicographically sorted (so each pair's blocks form one
    contiguous, disjoint range), every real edge appears exactly once,
    and ``block_first`` marks exactly the destination-bucket starts."""
    g = make()
    csc = build_csc_layout(g, block_v=block_v, block_e=block_e)
    src = np.asarray(csc.src).reshape(csc.n_edge_blocks, csc.block_e)
    dst = np.asarray(csc.dst).reshape(csc.n_edge_blocks, csc.block_e)
    nb = np.asarray(csc.block_nb)
    sb = np.asarray(csc.block_sb)
    first = np.asarray(csc.block_first)
    real = dst != g.n_nodes
    # every real directed edge exactly once (padding is sink->sink)
    assert real.sum() == g.n_edges
    assert (src[~real] == g.n_nodes).all()
    got = set(zip(src[real].tolist(), dst[real].tolist()))
    want = set(zip(np.asarray(g.src[: g.n_edges]).tolist(),
                   np.asarray(g.dst[: g.n_edges]).tolist()))
    assert got == want
    # per-block purity in BOTH coordinates — the property that lets the
    # kernel stage exactly one (block_v, B) source tile per edge block
    for k in range(csc.n_edge_blocks):
        r = real[k]
        assert (dst[k][r] // block_v == nb[k]).all()
        assert (src[k][r] // block_v == sb[k]).all()
    # the (nb, sb) pair key is non-decreasing => each pair's blocks are
    # one contiguous range, ranges are disjoint and ordered
    mult = sb.max() + 1
    pair = nb.astype(np.int64) * mult + sb
    assert (np.diff(pair) >= 0).all()
    # block_first: exactly the first block of each destination bucket
    want_first = np.zeros_like(first)
    want_first[0] = 1
    want_first[1:][np.diff(nb) != 0] = 1
    np.testing.assert_array_equal(first, want_first)
    assert first.sum() == csc.n_node_blocks


# ---------------------------------------------------------------------------
# The legacy direct-gather kernel (pre-staging formulation)
# ---------------------------------------------------------------------------

def _legacy_kernel(nb_ref, first_ref, act_ref, level_ref, src_ref, dst_ref,
                   dist_any, sigma_any, out_ref, *, block_v, block_e):
    k = pl.program_id(0)

    @pl.when(first_ref[k] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(act_ref[k] == 1)
    def _expand():
        src = src_ref[...]           # (block_e,) streamed by BlockSpec
        dst = dst_ref[...]
        levels = level_ref[...]
        # THE LEGACY MOVE: per-edge gather straight off the ANY refs —
        # interpret-only, exactly what the staged path eliminated
        vals = jnp.where(dist_any[src, :] == levels[None, :],
                         sigma_any[src, :], 0.0)       # (block_e, B)
        dst_local = dst - nb_ref[k] * block_v
        onehot = (dst_local[None, :] == jax.lax.broadcasted_iota(
            jnp.int32, (block_v, block_e), 0)).astype(jnp.float32)
        out_ref[...] += jnp.dot(onehot, vals,
                                preferred_element_type=jnp.float32)


def legacy_direct_gather(csc, dist, sigma, levels):
    """The node-blocked expansion with the pre-staging direct gather,
    on the SAME pair-bucketed layout (``block_sb`` unused).  State may
    carry more rows than ``csc.v_pad`` (the sharded wide lane); the
    output is always the (csc.v_pad, B) tile stack."""
    v_rows, batch = dist.shape
    levels = jnp.asarray(levels, jnp.int32).reshape(batch)
    if v_rows < csc.v_pad:
        dist = jnp.pad(dist, ((0, csc.v_pad - v_rows), (0, 0)),
                       constant_values=-3)
        sigma = jnp.pad(sigma, ((0, csc.v_pad - v_rows), (0, 0)))
    block_active = frontier_block_bitmap(csc, dist, levels)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,       # block_nb, block_first, block_active
        grid=(csc.n_edge_blocks,),
        in_specs=[
            pl.BlockSpec((batch,), lambda k, nb, first, act: (0,)),
            pl.BlockSpec((csc.block_e,), lambda k, nb, first, act: (k,)),
            pl.BlockSpec((csc.block_e,), lambda k, nb, first, act: (k,)),
            pl.BlockSpec(memory_space=pltpu.ANY),      # dist
            pl.BlockSpec(memory_space=pltpu.ANY),      # sigma
        ],
        out_specs=pl.BlockSpec((csc.block_v, batch),
                               lambda k, nb, first, act: (nb[k], 0)),
    )
    return pl.pallas_call(
        functools.partial(_legacy_kernel, block_v=csc.block_v,
                          block_e=csc.block_e),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((csc.v_pad, batch), jnp.float32),
        interpret=True,
    )(csc.block_nb, csc.block_first, block_active, levels,
      csc.src, csc.dst, dist, sigma)


# ---------------------------------------------------------------------------
# 3-way parity: staged vs legacy direct gather vs XLA reference
# ---------------------------------------------------------------------------

def test_three_way_parity_small_rmat():
    g = rmat_graph(9, 8, seed=1)
    csc = build_csc_layout(g, block_v=64, block_e=128)
    dist, sigma, levels = _bfs_state(g, 8, seed=1)
    ref = frontier_expand_batched_ref(g.src, g.dst, dist, sigma, levels)
    staged = frontier_expand_node_blocked_pallas(csc, dist, sigma, levels)
    legacy = legacy_direct_gather(csc, dist, sigma, levels)[: dist.shape[0]]
    np.testing.assert_array_equal(np.asarray(staged), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(staged), np.asarray(legacy))


def test_three_way_parity_above_flat_fit():
    """V * B above the flat kernel's VMEM predicate — the regime the
    staged kernel exists for — at the default blocking."""
    batch = 64
    g = grid_graph(126, 126)
    assert not pallas_supported(g.n_nodes, batch=batch)
    csc = build_csc_layout(g, batch=batch)
    dist, sigma, levels = _bfs_state(g, batch, seed=7)
    ref = frontier_expand_batched_ref(g.src, g.dst, dist, sigma, levels)
    staged = frontier_expand_node_blocked_pallas(csc, dist, sigma, levels)
    legacy = legacy_direct_gather(csc, dist, sigma, levels)[: dist.shape[0]]
    np.testing.assert_array_equal(np.asarray(staged), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(ref))


def test_three_way_parity_sharded_wide_state():
    """The sharded lane: each shard's local layout gathers from the
    GLOBAL row space (wide_state).  Staged, legacy, and the sharded XLA
    oracle must agree per shard on a synthesized gathered frontier."""
    g = grid_graph(32, 16)
    pg = partition_graph(g, 4, block_v=32, block_e=128)
    B = 3
    sources = jnp.asarray([0, 100, 511], jnp.int32)
    res = bfs_sssp_batched(g, sources)
    levels = jnp.asarray([1, 2, 3], jnp.int32)
    v1 = g.n_nodes + 1
    fvals = jnp.zeros((pg.v_pad, B), jnp.float32).at[:v1].set(
        jnp.where(res.dist == levels[None, :], res.sigma, 0.0))
    fdist = jnp.where(fvals > 0, levels[None, :], -1)
    for s in range(pg.n_shards):
        lcsc = pg.shards.shard(s)
        oracle = frontier_expand_sharded_ref(lcsc, fdist, fvals, levels)
        staged = frontier_expand_node_blocked_pallas(
            lcsc, fdist, fvals, levels, wide_state=True)[: pg.shard_rows]
        legacy = legacy_direct_gather(lcsc, fdist, fvals,
                                      levels)[: pg.shard_rows]
        np.testing.assert_array_equal(np.asarray(staged),
                                      np.asarray(oracle))
        np.testing.assert_array_equal(np.asarray(legacy),
                                      np.asarray(oracle))
