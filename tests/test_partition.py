"""The vertex-partitioned graph subsystem (DESIGN.md §Partitioning):
shard-layout integrity (every edge in exactly one shard, owner-map round
trips), bit-for-bit parity of the sharded frontier lane vs the
replicated ``frontier_expand`` route — including an 8-device mesh at a V
above the single-shard (flat-kernel) fit predicate — and end-to-end
``run_kadabra`` convergence on a ``PartitionedGraph`` against
``brandes_numpy``.

The multi-device cases run in subprocesses because the fake-device XLA
flag must be set before JAX initializes (this process keeps 1 device);
single-device cases exercise the same code paths on a 1-device mesh
in-process (collectives over one device are identities, but every
sharded lane — init, exchange, dispatch route, owner maps — still runs).
"""
import os
import subprocess
import sys
import textwrap
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (build_csc_layout, erdos_renyi_graph, grid_graph,
                        partition_graph, vertex_owner)
from repro.core.bfs import bfs_sssp_batched, bfs_sssp_batched_sharded
from repro.core.partition import (PartitionedGraph, abstract_partitioned_graph,
                                  auto_exchange_budget,
                                  default_exchange_budget, exchange_plan,
                                  global_row, max_active_source_chunks,
                                  shard_vertex_range)
from repro.kernels.frontier import (edge_bitmap_from_source_bits,
                                    frontier_block_bitmap, frontier_expand,
                                    frontier_expand_sharded_ref,
                                    select_route, sharded_supported)
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# Shard-layout integrity + owner maps (host-side, no mesh needed)
# ---------------------------------------------------------------------------

def _real_edges(pg):
    """(src_global, dst_global) pairs over all shards, padding stripped."""
    out = []
    for s in range(pg.n_shards):
        src = np.asarray(pg.shards.src[s])
        dst = np.asarray(pg.shards.dst[s])
        real = src != pg.n_nodes
        out.append(np.stack([src[real], dst[real] + s * pg.shard_rows], 1))
    return out


@pytest.mark.parametrize("n_shards", [1, 3, 4])
def test_every_edge_in_exactly_one_shard(n_shards):
    g = erdos_renyi_graph(500, 6.0, seed=7)
    pg = partition_graph(g, n_shards, block_v=64, block_e=128)
    per_shard = _real_edges(pg)
    got = np.concatenate(per_shard)
    want = np.stack([np.asarray(g.src[: g.n_edges]),
                     np.asarray(g.dst[: g.n_edges])], 1)
    assert got.shape == want.shape                      # exactly once
    got_set = set(map(tuple, got.tolist()))
    assert got_set == set(map(tuple, want.tolist()))
    # destination ownership: each shard holds exactly the edges INTO its
    # vertex range
    for s, edges in enumerate(per_shard):
        lo, hi = shard_vertex_range(pg, s)
        assert ((edges[:, 1] >= lo) & (edges[:, 1] < hi)).all()
    # local dst rows stay inside [0, shard_rows] (shard_rows = padding)
    for s in range(pg.n_shards):
        dst = np.asarray(pg.shards.dst[s])
        assert dst.max() <= pg.shard_rows


def test_owner_map_round_trip():
    g = grid_graph(24, 16)
    pg = partition_graph(g, 4, block_v=32, block_e=128)
    v = np.arange(pg.n_nodes)
    s = vertex_owner(pg, v)
    # round trip: global_row(owner, local) == vertex id
    np.testing.assert_array_equal(
        global_row(pg, s, v - s * pg.shard_rows), v)
    # ranges tile the padded row space
    assert shard_vertex_range(pg, 0)[0] == 0
    for i in range(pg.n_shards - 1):
        assert shard_vertex_range(pg, i)[1] == shard_vertex_range(pg, i + 1)[0]
    assert shard_vertex_range(pg, pg.n_shards - 1)[1] == pg.v_pad
    # shard boundaries are whole node blocks
    assert pg.shard_rows % pg.shards.block_v == 0
    # the sink row is owned (v_pad covers n_nodes + 1)
    assert pg.v_pad >= pg.n_nodes + 1


def test_shard_bytes_scale_down():
    """The memory claim at construction level: per-shard frontier-lane
    bytes <= (1/n_shards + eps) of the replicated CSCLayout (eps covers
    per-bucket block padding)."""
    n_shards = 8
    g = erdos_renyi_graph(1 << 13, 4.0, seed=3)
    csc = build_csc_layout(g, block_v=256, block_e=256)
    pg = partition_graph(g, n_shards, block_v=256, block_e=256)
    rep = sum(int(np.asarray(a).nbytes) for a in
              (csc.src, csc.dst, csc.block_nb, csc.block_first))
    per_dev = sum(int(np.asarray(a).nbytes) for a in
                  (pg.shards.src, pg.shards.dst, pg.shards.block_nb,
                   pg.shards.block_first)) // n_shards
    assert per_dev <= rep * (1.0 / n_shards + 0.2), (per_dev, rep)


def test_abstract_partitioned_graph_matches_builder_structure():
    """The dry-run's ShapeDtypeStruct twin must carry the same statics
    and leaf structure as a real partition (so lowering the sharded
    epoch exercises the real pytree)."""
    g = erdos_renyi_graph(2000, 4.0, seed=1)
    pg = partition_graph(g, 4, block_v=64, block_e=128)
    ab = abstract_partitioned_graph(g.n_nodes, g.n_edges, 4,
                                    block_v=64, block_e=128)
    assert ab.n_shards == pg.n_shards
    assert ab.shard_rows == pg.shard_rows
    assert ab.v_pad == pg.v_pad
    # same leaf structure/dtypes (edge-slot counts may over-estimate:
    # the abstract twin sizes padding conservatively)
    ab_leaves = jax.tree_util.tree_leaves(ab)
    pg_leaves = jax.tree_util.tree_leaves(pg)
    assert len(ab_leaves) == len(pg_leaves)
    for a, b in zip(ab_leaves, pg_leaves):
        assert a.dtype == b.dtype and len(a.shape) == len(b.shape)
    assert ab.shards.n_edge_blocks >= pg.shards.n_edge_blocks


# ---------------------------------------------------------------------------
# Dispatcher: the sharded route + its fit predicate
# ---------------------------------------------------------------------------

def test_select_route_sharded():
    g = erdos_renyi_graph(400, 6.0, seed=2)
    pg = partition_graph(g, 2, block_v=64, block_e=128)
    lcsc = pg.shards.shard(0)
    assert sharded_supported(lcsc, 8)
    kw = dict(csc=None, shard=lcsc)
    assert select_route(400, 8, interpret=True, **kw) == "sharded_ref"
    assert select_route(400, 8, interpret=False, **kw) == "sharded_nb"
    assert select_route(400, 8, use_pallas=False, **kw) == "sharded_ref"
    assert select_route(400, 8, use_pallas="node_blocked",
                        **kw) == "sharded_nb"
    with pytest.raises(ValueError, match="flat"):
        select_route(400, 8, use_pallas=True, **kw)


def test_sharded_expand_lanes_agree_with_restricted_global():
    """Both sharded lanes (XLA ref and wide-state node-blocked kernel)
    must reproduce the replicated expansion restricted to the shard's
    rows, bit-for-bit, from a synthesized gathered frontier."""
    g = grid_graph(32, 16)
    pg = partition_graph(g, 4, block_v=32, block_e=128)
    B = 3
    sources = jnp.asarray([0, 100, 511], jnp.int32)
    res = bfs_sssp_batched(g, sources)
    levels = jnp.asarray([1, 2, 3], jnp.int32)
    # gathered frontier contract: masked values over the global rows
    v1 = g.n_nodes + 1
    fvals = jnp.zeros((pg.v_pad, B), jnp.float32).at[:v1].set(
        jnp.where(res.dist == levels[None, :], res.sigma, 0.0))
    fdist = jnp.where(fvals > 0, levels[None, :], -1)
    ref_full = frontier_expand(g.src, g.dst, res.dist, res.sigma, levels,
                               use_pallas=False)
    for s in range(pg.n_shards):
        lcsc = pg.shards.shard(s)
        lo, hi = shard_vertex_range(pg, s)
        want = np.zeros((pg.shard_rows, B), np.float32)
        cut = np.asarray(ref_full)[lo:min(hi, v1)]
        want[: cut.shape[0]] = cut
        out_ref = frontier_expand(lcsc.src, lcsc.dst, fdist, fvals, levels,
                                  shard=lcsc, use_pallas=False)
        out_nb = frontier_expand(lcsc.src, lcsc.dst, fdist, fvals, levels,
                                 shard=lcsc, use_pallas="node_blocked")
        oracle = frontier_expand_sharded_ref(lcsc, fdist, fvals, levels)
        np.testing.assert_array_equal(np.asarray(out_ref), want)
        np.testing.assert_array_equal(np.asarray(out_nb), want)
        np.testing.assert_array_equal(np.asarray(oracle), want)


# ---------------------------------------------------------------------------
# Exchange schedule: budget defaults, per-level accounting, bitmaps
# ---------------------------------------------------------------------------

def test_default_exchange_budget_contract():
    # ceil(cps / 4), clamped into [0, cps - 1]; one-chunk shards are
    # dense-only
    assert default_exchange_budget(1) == 0
    assert default_exchange_budget(2) == 1
    assert default_exchange_budget(5) == 2
    assert default_exchange_budget(33) == 9
    g = grid_graph(16, 8)
    pg = partition_graph(g, 1, block_v=32, block_e=128)
    # chunk granularity divides the node block and shard rows
    assert pg.shards.block_v % pg.exchange_chunk_rows == 0
    assert pg.shard_rows % pg.exchange_chunk_rows == 0
    assert 0 <= pg.exchange_budget < pg.exchange_chunks_per_shard
    # explicit budgets are clamped, 0 disables
    assert partition_graph(g, 1, block_v=32, block_e=128,
                           exchange_budget=10**6).exchange_budget \
        == pg.exchange_chunks_per_shard - 1
    assert partition_graph(g, 1, block_v=32, block_e=128,
                           exchange_budget=0).exchange_budget == 0
    ab = abstract_partitioned_graph(g.n_nodes, g.n_edges, 1,
                                    block_v=32, block_e=128)
    assert ab.exchange_budget == pg.exchange_budget


def test_auto_exchange_budget_rule():
    """The ``exchange_budget="auto"`` derivation: quantile order
    statistic over observed worst-shard occupancies, then the same
    structural clamp as an explicit budget; empty observations fall
    back to the default policy."""
    g = grid_graph(128, 16)
    pg = partition_graph(g, 4, block_v=64, block_e=128)
    cps = pg.exchange_chunks_per_shard
    assert cps >= 4  # the cases below need clamp headroom
    # q=0.9 over 10 ascending observations picks (about) the 9th-ranked
    occ = list(range(1, 11))
    assert auto_exchange_budget(pg, occ, quantile=0.9) == min(9, cps - 1)
    # the median rule and order-independence
    assert auto_exchange_budget(pg, [3, 1, 2], quantile=0.5) == 2
    assert auto_exchange_budget(pg, [2, 3, 1], quantile=0.5) == 2
    # clamp contract: huge observed occupancies cap at cps - 1, and a
    # quantile of 0 picks the smallest observation
    assert auto_exchange_budget(pg, [10**6], quantile=0.9) == cps - 1
    assert auto_exchange_budget(pg, [1, 10**6], quantile=0.0) == 1
    # empty observations -> the static default policy
    assert auto_exchange_budget(pg, []) == default_exchange_budget(cps)
    # partition_graph accepts the sentinel: default budget now, flag
    # set for the driver to swap in the derived one post-diameter
    pga = partition_graph(g, 4, block_v=64, block_e=128,
                          exchange_budget="auto")
    assert pga.exchange_budget_auto
    assert pga.exchange_budget == default_exchange_budget(cps)
    ab = abstract_partitioned_graph(g.n_nodes, g.n_edges, 4, block_v=64,
                                    block_e=128, exchange_budget="auto")
    assert ab.exchange_budget_auto


def test_exchange_volume_accounting():
    """The satellite acceptance numbers: on a high-diameter (narrow)
    grid the reported per-level exchange bytes are <= the dense
    baseline everywhere, strictly below in aggregate, and exactly ==
    dense on fallback (over-budget) levels."""
    g = grid_graph(512, 8)                      # diameter ~518
    B = 4
    pg = partition_graph(g, 4, block_v=64, block_e=128)
    plan = exchange_plan(pg, B)
    assert plan.budget == default_exchange_budget(pg.exchange_chunks_per_shard)
    assert plan.sparse_bytes < plan.dense_bytes
    rng = np.random.default_rng(0)
    sources = jnp.asarray(rng.integers(0, g.n_nodes, B), jnp.int32)
    res = jax.jit(bfs_sssp_batched)(g, sources)
    dist = np.asarray(res.dist)
    depth = int(np.asarray(res.levels).max())
    total = dense_total = 0
    n_sparse = n_fallback = 0
    for lv in range(depth + 1):
        mab = max_active_source_chunks(pg, (dist == lv).any(axis=1))
        got = plan.level_bytes(mab)
        assert got <= plan.dense_bytes
        if plan.sparse_taken(mab):
            assert got == plan.sparse_bytes
            n_sparse += 1
        else:
            # fallback path: reported bytes == the dense baseline
            assert got == plan.dense_bytes
            n_fallback += 1
        total += got
        dense_total += plan.dense_bytes
    # both protocols exercised on this instance, aggregate strictly
    # below the dense baseline (O(frontier) scaling across levels)
    assert n_sparse > 0
    assert total < dense_total
    # a one-level full frontier (every row active) always falls back
    assert plan.level_bytes(pg.exchange_chunks_per_shard) \
        == plan.dense_bytes


def test_derived_edge_bitmap_conservative_and_parity():
    """The exchange schedule's source-chunk bits, coarsened to the
    kernel's edge-block bitmap, must cover the exact bitmap (superset)
    and leave the node-blocked kernel output bit-identical."""
    g = grid_graph(32, 16)
    csc = build_csc_layout(g, block_v=64, block_e=128)
    sources = jnp.asarray([0, 100, 511], jnp.int32)
    res = bfs_sssp_batched(g, sources)
    levels = jnp.asarray([2, 3, 5], jnp.int32)
    dist = res.dist
    chunk = 64
    mask = jnp.any(dist == levels[None, :], axis=1)      # (V+1,)
    mask = jnp.pad(mask, (0, csc.v_pad - mask.shape[0]))
    bits = jnp.max(mask.reshape(-1, chunk).astype(jnp.int32), axis=1)
    derived = edge_bitmap_from_source_bits(csc, bits, chunk)
    exact = frontier_block_bitmap(csc, dist, levels)
    assert (np.asarray(derived) >= np.asarray(exact)).all()
    out_exact = frontier_expand(g.src, g.dst, dist, res.sigma, levels,
                                csc=csc, use_pallas="node_blocked")
    out_derived = frontier_expand(g.src, g.dst, dist, res.sigma, levels,
                                  csc=csc, use_pallas="node_blocked",
                                  block_active=derived)
    np.testing.assert_array_equal(np.asarray(out_exact),
                                  np.asarray(out_derived))


# ---------------------------------------------------------------------------
# Single-device mesh: the sharded driver end-to-end (n_shards = 1)
# ---------------------------------------------------------------------------

def test_sharded_bfs_parity_one_shard():
    """Parity on a 1-device mesh — collectives are identities, but the
    whole sparse exchange (bitmap, compaction, scatter-reconstruction,
    cond fallback) runs in-process: the default budget engages the
    sparse protocol on narrow levels of this grid and falls back on
    wide ones, and a dense-only partition of the same graph must
    produce bit-identical results."""
    g = grid_graph(16, 8)
    pg = partition_graph(g, 1, block_v=32, block_e=128)
    assert pg.exchange_budget > 0          # sparse protocol reachable
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sources = jnp.asarray([0, 64, 127], jnp.int32)

    def run_on(pgraph):
        gspec = pgraph.partition_spec(("data",))

        @jax.jit
        @partial(jax.shard_map, mesh=mesh, in_specs=(gspec,),
                 out_specs=(P("data"), P("data"), P()), check_vma=False)
        def run(pgl):
            r = bfs_sssp_batched_sharded(pgl, sources, axis=("data",))
            return r.dist, r.sigma, r.levels

        return run(pgraph)

    d, sg, lv = run_on(pg)
    ref = bfs_sssp_batched(g, sources)
    v1 = g.n_nodes + 1
    np.testing.assert_array_equal(np.asarray(d[:v1]), np.asarray(ref.dist))
    np.testing.assert_array_equal(np.asarray(sg[:v1]), np.asarray(ref.sigma))
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(ref.levels))
    # dense-only lane (exchange_budget=0): bit-for-bit the same
    d0, sg0, lv0 = run_on(partition_graph(g, 1, block_v=32, block_e=128,
                                          exchange_budget=0))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d0))
    np.testing.assert_array_equal(np.asarray(sg), np.asarray(sg0))
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(lv0))


def test_run_kadabra_partitioned_requires_mesh():
    g = grid_graph(8, 8)
    pg = partition_graph(g, 2, block_v=16, block_e=128)
    from repro.core import run_kadabra
    with pytest.raises(ValueError, match="mesh"):
        run_kadabra(pg)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    with pytest.raises(ValueError, match="shards"):
        run_kadabra(pg, mesh=mesh)


# ---------------------------------------------------------------------------
# 8-device mesh (subprocess): parity above the flat fit predicate +
# end-to-end convergence on a PartitionedGraph
# ---------------------------------------------------------------------------

_MESH8_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from functools import partial
    import jax, jax.numpy as jnp
    import numpy as np
    import networkx as nx
    from jax.sharding import PartitionSpec as P
    from repro.core import (AdaptiveConfig, brandes_numpy, erdos_renyi_graph,
                            from_edge_list, partition_graph, run_kadabra,
                            sample_batch)
    from repro.core.bfs import (bfs_sssp_batched, bfs_sssp_batched_sharded,
                                bidirectional_bfs_batched,
                                bidirectional_bfs_batched_sharded)
    from repro.core.diameter import estimate_diameter, estimate_diameter_sharded
    from repro.kernels.frontier import pallas_supported

    axes = ("data",)
    mesh = jax.make_mesh((8,), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))

    # --- batched BFS parity at V ABOVE the single-shard fit predicate ---
    # (grid instance: the staged gather's pair-bucketed layout targets
    # source-locality-friendly graphs, the paper's road networks)
    from repro.core import grid_graph
    B = 64
    g = grid_graph(126, 126)
    assert not pallas_supported(g.n_nodes, batch=B)
    pg = partition_graph(g, 8, batch=B)
    gspec = pg.partition_spec(axes)
    rng = np.random.default_rng(11)
    sources = jnp.asarray(rng.integers(0, g.n_nodes, B), jnp.int32)

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(gspec,),
             out_specs=(P("data"), P("data"), P()), check_vma=False)
    def run_bfs(pgl):
        r = bfs_sssp_batched_sharded(pgl, sources, axis=axes)
        return r.dist, r.sigma, r.levels

    d, s, lv = run_bfs(pg)
    ref = jax.jit(bfs_sssp_batched)(g, sources)
    v1 = g.n_nodes + 1
    np.testing.assert_array_equal(np.asarray(d[:v1]), np.asarray(ref.dist))
    np.testing.assert_array_equal(np.asarray(s[:v1]), np.asarray(ref.sigma))
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(ref.levels))
    # rows past the logical range are inert
    assert (np.asarray(d[v1:]) == -3).all()
    assert (np.asarray(s[v1:]) == 0).all()
    print("OK bfs_parity_over_budget")

    # --- bidirectional + diameter + sampler parity on a grid ------------
    from repro.core import grid_graph
    g2 = grid_graph(64, 32)
    pg2 = partition_graph(g2, 8, block_v=128, block_e=256)
    gspec2 = pg2.partition_spec(axes)
    ss = jnp.asarray([0, 5, 1000, 2047], jnp.int32)
    tt = jnp.asarray([2047, 100, 9, 44], jnp.int32)

    def run_bidir(pgraph):
        # specs are built per graph: the PartitionedGraph treedef carries
        # the static exchange_budget, so a spec tree from one budget
        # cannot serve a graph with another
        @jax.jit
        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(pgraph.partition_spec(axes),),
                 out_specs=(P("data"),) * 4 + (P(), P()), check_vma=False)
        def run(pgl):
            r = bidirectional_bfs_batched_sharded(pgl, ss, tt, axis=axes)
            return r.dist_s, r.dist_t, r.sigma_s, r.sigma_t, r.d, r.split

        return run(pgraph)

    got = run_bidir(pg2)
    want = jax.jit(bidirectional_bfs_batched)(g2, ss, tt)
    v1 = g2.n_nodes + 1
    for a, b in zip((got[0][:v1], got[1][:v1], got[2][:v1], got[3][:v1],
                     got[4], got[5]), want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("OK bidir_parity")

    # --- dense vs bitmap-scheduled exchange: bit-identical on the mesh --
    # (pg2's default budget engages the sparse protocol on narrow levels
    # and falls back on wide ones; a dense-only partition of the same
    # graph must produce the same bits everywhere, padding included)
    assert pg2.exchange_budget > 0
    pg2_dense = partition_graph(g2, 8, block_v=128, block_e=256,
                                exchange_budget=0)
    got_dense = run_bidir(pg2_dense)
    for a, b in zip(got, got_dense):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("OK exchange_protocol_parity")

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(gspec2,), out_specs=P(),
             check_vma=False)
    def run_diam(pgl):
        return estimate_diameter_sharded(pgl, axis=axes).vertex_diameter

    assert int(run_diam(pg2)) == int(
        jax.jit(estimate_diameter)(g2).vertex_diameter)
    print("OK diameter_parity")

    key = jax.random.PRNGKey(5)

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(gspec2, P()),
             out_specs=(P(), P()), check_vma=False)
    def run_samp(pgl, k):
        return sample_batch(pgl, k, 19, batch_size=6, axis=axes)

    c_sh, t_sh = run_samp(pg2, key)
    c_rep, t_rep = jax.jit(
        partial(sample_batch, n_samples=19, batch_size=6))(g2, key)
    np.testing.assert_array_equal(np.asarray(c_sh), np.asarray(c_rep))
    assert int(t_sh) == int(t_rep) == 19
    print("OK sampler_parity")

    # --- end-to-end: run_kadabra on a PartitionedGraph ------------------
    G = nx.connected_watts_strogatz_graph(60, 6, 0.3, seed=0)
    g3 = from_edge_list(np.array(G.edges()), 60)
    pg3 = partition_graph(g3, 8, block_v=8, block_e=128)
    eps = 0.05
    cfg = AdaptiveConfig(eps=eps, delta=0.1, n0_base=400)
    mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                          axis_types=(jax.sharding.AxisType.Auto,) * 3)
    res = run_kadabra(pg3, mesh=mesh3, config=cfg, key=jax.random.PRNGKey(0))
    exact = brandes_numpy(g3)
    err = np.abs(res.btilde - exact).max()
    assert err < eps, f"max err {err:.4f} >= eps {eps}"
    assert res.converged and res.tau > 0
    print(f"OK kadabra_partitioned err={err:.4f} tau={res.tau}")

    # --- exchange_budget="auto": derived post-diameter, same bits -------
    # (the driver swaps in the occupancy-derived budget before
    # calibration; the protocol choice never changes BFS results, so
    # the whole run stays bit-identical to the static-budget one)
    pg3_auto = partition_graph(g3, 8, block_v=8, block_e=128,
                               exchange_budget="auto")
    assert pg3_auto.exchange_budget_auto
    res_auto = run_kadabra(pg3_auto, mesh=mesh3, config=cfg,
                           key=jax.random.PRNGKey(0))
    np.testing.assert_array_equal(res_auto.btilde, res.btilde)
    assert res_auto.converged and res_auto.tau == res.tau
    print("OK kadabra_auto_budget")

    # --- checkpoint/resume on the sharded lane --------------------------
    import dataclasses as dc
    import tempfile
    assert res.n_epochs >= 2
    ck = tempfile.mkdtemp()
    part = run_kadabra(pg3, mesh=mesh3,
                       config=dc.replace(cfg, max_epochs=1),
                       key=jax.random.PRNGKey(0), checkpoint_dir=ck)
    assert not part.converged
    resumed = run_kadabra(pg3, mesh=mesh3, config=cfg,
                          key=jax.random.PRNGKey(0), checkpoint_dir=ck)
    np.testing.assert_array_equal(resumed.btilde, res.btilde)
    assert resumed.tau == res.tau and resumed.converged
    print("OK kadabra_partitioned_resume")
""")


def test_partitioned_mesh8_subprocess():
    """Parity + end-to-end acceptance on an 8-device host mesh (sharded
    state through the whole while_loop; V above the flat kernel's fit
    predicate; cooperative run_kadabra on the (pod, data, model) mesh)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _MESH8_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert out.stdout.count("OK") == 8


# ---------------------------------------------------------------------------
# partition_sweep smoke (tier-1 guard for the benchmark section)
# ---------------------------------------------------------------------------

def test_partition_sweep_smoke():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.run import run_partition_sweep
    rec = run_partition_sweep([10], grid_scales=[10], n_dev=4, batch=4,
                              n_samples=8, write_json=False)
    assert rec["section"] == "partition_sweep"
    er_row, grid_row = rec["results"]
    assert er_row["family"] == "erdos_renyi"
    assert grid_row["family"] == "grid"
    for row in (er_row, grid_row):
        assert row["bytes_ratio"] <= 1.0 / row["n_dev"] + 0.2
        assert row["bfs_depth"] > 1
        assert len(row["exchange_per_level"]) == row["bfs_depth"] + 1
        assert row["samples_per_s_sharded"] > 0
        # per-level exchange accounting: never above the dense baseline,
        # == dense exactly on fallback levels
        for lv in row["exchange_per_level"]:
            assert lv["exchange_bytes"] <= lv["dense_gather_bytes"]
            if not lv["sparse_taken"]:
                assert lv["exchange_bytes"] == lv["dense_gather_bytes"]
        assert row["exchange_bytes_total"] <= row["dense_bytes_total"]
    # the high-diameter grid engages the sparse protocol: strictly
    # below the dense baseline in aggregate
    assert grid_row["exchange_budget_blocks"] > 0
    assert any(lv["sparse_taken"] for lv in grid_row["exchange_per_level"])
    assert grid_row["exchange_bytes_total"] < grid_row["dense_bytes_total"]
