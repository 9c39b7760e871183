"""Golden parity suite for the estimator-plugin substrate.

The engine refactor's acceptance bar is bit-for-bit: the plugin-driven
``run_kadabra`` must reproduce the pre-refactor inline drivers exactly,
on every lane.  ``_LEGACY_SRC`` below freezes a condensation of those
drivers (sample stream, key flow and arithmetic verbatim; checkpoint
and timing bookkeeping dropped) — it is executed as an independent
reference implementation, never imported from the package, so a drift
in the engine cannot silently drift the reference with it.

Alongside the legacy parity: the "closeness" / "harmonic" plugins
against dense scipy oracles, the multi-estimator mode against its solo
runs (bit-equality — the shared stream must not perturb any metric),
the single-BFS-stream claim via an HLO while-instruction census, the
fixed-sampling route through the engine, and the checkpoint schema
guard.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import (AdaptiveConfig, erdos_renyi_graph, grid_graph,
                        run_fixed_sampling, run_kadabra)

_LEGACY_SRC = r"""
# ---- frozen PR 1-6 betweenness drivers (condensed, arithmetic verbatim)
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import distributed as dist
from repro.core.adaptive import (DEFAULT_SAMPLE_BATCH_SIZE,
                                 resolve_sample_batch_size)
from repro.core.diameter import estimate_diameter, estimate_diameter_sharded
from repro.core.engine import _pad_len
from repro.core.epoch import StateFrame, epoch_length, zero_frame
from repro.core.kadabra import (KadabraParams, calibrate_deltas, check_stop,
                                compute_omega)
from repro.core.sampler import sample_batch


def _legacy_params(graph, cfg, vd, btilde0):
    omega = compute_omega(vd, cfg.eps, cfg.delta)
    lil, liu, _ = calibrate_deltas(btilde0, cfg.eps, cfg.delta, omega)
    return KadabraParams(cfg.eps, cfg.delta, omega, lil, liu)


def legacy_run_single(graph, cfg, key):
    v_pad = _pad_len(graph.n_nodes, 1)
    diam = jax.jit(partial(estimate_diameter,
                           n_sweeps=cfg.diameter_sweeps))(graph)
    vd = int(diam.vertex_diameter)
    bsz = resolve_sample_batch_size(cfg.sample_batch_size,
                                    graph.n_nodes, vd)
    key, k_cal = jax.random.split(key)
    counts0, tau0 = jax.jit(partial(
        sample_batch, n_samples=cfg.calib_samples_per_device,
        batch_size=bsz))(graph, k_cal)
    btilde0 = (counts0[: graph.n_nodes]
               / jnp.maximum(tau0.astype(jnp.float32), 1.0))
    params = jax.jit(partial(_legacy_params, cfg=cfg))(graph, vd=vd,
                                                       btilde0=btilde0)
    n0 = epoch_length(1, base=cfg.n0_base, exponent=cfg.n0_exponent)
    v1 = graph.n_nodes + 1

    @jax.jit
    def epoch_step(agg_counts, agg_tau, frame_counts, frame_tau,
                   sur_counts, sur_tau, k):
        agg_counts = agg_counts + frame_counts
        agg_tau = agg_tau + frame_tau
        (c, t), (sc, st) = sample_batch(graph, k, n0, batch_size=bsz,
                                        carry=(sur_counts, sur_tau),
                                        return_carry=True)
        new_counts = jnp.zeros((v_pad,),
                               jnp.float32).at[: c.shape[0]].set(c)
        done, mf, mg = check_stop(agg_counts[: graph.n_nodes], agg_tau,
                                  params)
        return agg_counts, agg_tau, new_counts, t, sc, st, done, mf, mg

    agg, frame = zero_frame(v_pad), zero_frame(v_pad)
    sur_counts, sur_tau = jnp.zeros((v1,), jnp.float32), jnp.int32(0)
    done, epoch, k = False, 0, key
    while not done and epoch < cfg.max_epochs:
        k, ke = jax.random.split(k)
        ac, at, fc, ft, sur_counts, sur_tau, done_dev, mf, mg = epoch_step(
            agg.counts, agg.tau, frame.counts, frame.tau,
            sur_counts, sur_tau, ke)
        agg, frame = StateFrame(ac, at), StateFrame(fc, ft)
        done = bool(done_dev)
        epoch += 1
    agg = agg + frame
    agg = StateFrame(agg.counts.at[:v1].add(sur_counts),
                     agg.tau + sur_tau)
    tau = int(agg.tau)
    btilde = np.asarray(agg.counts[: graph.n_nodes]) / max(tau, 1)
    return btilde, tau, epoch, bool(done), float(params.omega), vd


def legacy_run_fixed(graph, n_samples, key=None, batch_size=None):
    if key is None:
        key = jax.random.PRNGKey(0)
    if batch_size is None:
        batch_size = DEFAULT_SAMPLE_BATCH_SIZE
    counts, tau = jax.jit(partial(sample_batch, n_samples=n_samples,
                                  batch_size=batch_size))(graph, key)
    return np.asarray(counts[: graph.n_nodes]) / max(int(tau), 1)


def _legacy_agg_fn(mesh, aggregation):
    all_axes = tuple(mesh.axis_names)
    local_axes, global_axes = dist.sampler_axes(mesh)
    if aggregation == "hierarchical":
        return lambda x: dist.hierarchical_allreduce(x, local_axes,
                                                     global_axes)
    if aggregation == "flat":
        return lambda x: dist.flat_allreduce(x, all_axes)
    return lambda x: dist.reduce_to_root_and_broadcast(x, all_axes)


def legacy_run_spmd(graph, cfg, key, mesh):
    all_axes = tuple(mesh.axis_names)
    n_dev = int(np.prod(mesh.devices.shape))
    v_pad = _pad_len(graph.n_nodes, n_dev)
    agg_fn = _legacy_agg_fn(mesh, cfg.aggregation)
    rep, frame_spec, key_spec = P(), P(all_axes, None), P(all_axes)
    gspec = jax.tree.map(lambda _: rep, graph)

    diam = jax.jit(partial(estimate_diameter,
                           n_sweeps=cfg.diameter_sweeps))(graph)
    vd = int(diam.vertex_diameter)
    bsz = resolve_sample_batch_size(cfg.sample_batch_size,
                                    graph.n_nodes, vd)

    @partial(jax.shard_map, mesh=mesh, in_specs=(gspec, key_spec),
             out_specs=(rep, rep), check_vma=False)
    def calib_step(g, keys):
        c, t = sample_batch(g, keys[0], cfg.calib_samples_per_device,
                            batch_size=bsz)
        cp = jnp.zeros((v_pad,), jnp.float32).at[: c.shape[0]].set(c)
        return (dist.flat_allreduce(cp, all_axes),
                dist.flat_allreduce(t, all_axes))

    key, k_cal = jax.random.split(key)
    counts0, tau0 = jax.jit(calib_step)(graph,
                                        jax.random.split(k_cal, n_dev))
    btilde0 = (counts0[: graph.n_nodes]
               / jnp.maximum(tau0.astype(jnp.float32), 1.0))
    params = jax.jit(partial(_legacy_params, cfg=cfg))(graph, vd=vd,
                                                       btilde0=btilde0)
    n0 = epoch_length(n_dev, base=cfg.n0_base, exponent=cfg.n0_exponent)
    v1 = graph.n_nodes + 1
    n_nodes = graph.n_nodes

    def epoch_step(g, params, agg_counts, agg_tau, frame_counts,
                   frame_tau, sur_counts, sur_tau, keys):
        pspec = jax.tree.map(lambda _: rep, params)

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(gspec, pspec, rep, rep, frame_spec, rep,
                           frame_spec, rep, key_spec),
                 out_specs=(rep, rep, frame_spec, rep, frame_spec, rep,
                            rep, rep, rep),
                 check_vma=False)
        def _step(g, params, agg_counts, agg_tau, frame_counts,
                  frame_tau, sur_counts, sur_tau, keys):
            inc_counts = agg_fn(frame_counts[0])
            inc_tau = dist.flat_allreduce(frame_tau, all_axes)
            (c, t), (sc, st) = sample_batch(g, keys[0], n0,
                                            batch_size=bsz,
                                            carry=(sur_counts[0],
                                                   sur_tau),
                                            return_carry=True)
            new_counts = jnp.zeros(
                (1, v_pad), jnp.float32).at[0, : c.shape[0]].set(c)
            agg_counts = agg_counts + inc_counts
            agg_tau = agg_tau + inc_tau
            done, mf, mg = check_stop(agg_counts[:n_nodes], agg_tau,
                                      params)
            return (agg_counts, agg_tau, new_counts, t, sc[None, :], st,
                    done, mf, mg)

        return _step(g, params, agg_counts, agg_tau, frame_counts,
                     frame_tau, sur_counts, sur_tau, keys)

    epoch_jit = jax.jit(epoch_step)
    agg_counts, agg_tau = jnp.zeros((v_pad,), jnp.float32), jnp.int32(0)
    frame_counts = jax.device_put(jnp.zeros((n_dev, v_pad), jnp.float32),
                                  NamedSharding(mesh, frame_spec))
    frame_tau = jnp.int32(0)
    sur_counts = jax.device_put(jnp.zeros((n_dev, v1), jnp.float32),
                                NamedSharding(mesh, frame_spec))
    sur_tau = jnp.int32(0)
    done, epoch, k = False, 0, key
    while not done and epoch < cfg.max_epochs:
        k, ke = jax.random.split(k)
        dev_keys = jax.device_put(jax.random.split(ke, n_dev),
                                  NamedSharding(mesh, key_spec))
        (agg_counts, agg_tau, frame_counts, frame_tau, sur_counts,
         sur_tau, done_dev, mf, mg) = epoch_jit(
            graph, params, agg_counts, agg_tau, frame_counts, frame_tau,
            sur_counts, sur_tau, dev_keys)
        done = bool(done_dev)
        epoch += 1

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(frame_spec, rep, frame_spec, rep),
             out_specs=(rep, rep), check_vma=False)
    def flush(frame_counts, frame_tau, sur_counts, sur_tau):
        c = frame_counts[0].at[:v1].add(sur_counts[0])
        return agg_fn(c), dist.flat_allreduce(frame_tau + sur_tau,
                                              all_axes)

    inc_c, inc_t = jax.jit(flush)(frame_counts, frame_tau,
                                  sur_counts, sur_tau)
    agg_counts = agg_counts + inc_c
    agg_tau = agg_tau + inc_t
    tau = int(agg_tau)
    btilde = np.asarray(agg_counts[: graph.n_nodes]) / max(tau, 1)
    return btilde, tau, epoch, bool(done), float(params.omega), vd


def legacy_run_sharded(pg, cfg, key, mesh):
    all_axes = tuple(mesh.axis_names)
    n_dev = int(np.prod(mesh.devices.shape))
    rep = P()
    gspec = pg.partition_spec(all_axes)
    v_pad = _pad_len(pg.n_nodes, n_dev)
    v1 = pg.n_nodes + 1
    want_dist = pg.exchange_budget_auto

    @partial(jax.shard_map, mesh=mesh, in_specs=(gspec,),
             out_specs=(rep, P(all_axes)) if want_dist else rep,
             check_vma=False)
    def diam_step(g):
        est = estimate_diameter_sharded(g, n_sweeps=cfg.diameter_sweeps,
                                        axis=all_axes,
                                        return_dist=want_dist)
        if want_dist:
            est, d = est
            return est.vertex_diameter, d
        return est.vertex_diameter

    if want_dist:
        from repro.core.partition import (auto_exchange_budget,
                                          max_active_source_chunks)
        vd_dev, dist_dev = jax.jit(diam_step)(pg)
        vd = int(vd_dev)
        dist_np = np.asarray(dist_dev)
        occupancies = []
        for lvl in range(int(dist_np.max(initial=-1)) + 1):
            rows = (dist_np == lvl).any(axis=1)
            if rows.any():
                occupancies.append(max_active_source_chunks(pg, rows))
        pg = dataclasses.replace(
            pg, exchange_budget=auto_exchange_budget(pg, occupancies),
            exchange_budget_auto=False)
        gspec = pg.partition_spec(all_axes)
    else:
        vd = int(jax.jit(diam_step)(pg))
    bsz = resolve_sample_batch_size(cfg.sample_batch_size, pg.n_nodes, vd)
    n_cal = cfg.calib_samples_per_device * n_dev

    @partial(jax.shard_map, mesh=mesh, in_specs=(gspec, rep),
             out_specs=(rep, rep), check_vma=False)
    def calib_step(g, k):
        c, t = sample_batch(g, k, n_cal, batch_size=bsz, axis=all_axes)
        cp = jnp.zeros((v_pad,), jnp.float32).at[: c.shape[0]].set(c)
        return cp, t

    key, k_cal = jax.random.split(key)
    counts0, tau0 = jax.jit(calib_step)(pg, k_cal)
    btilde0 = (counts0[: pg.n_nodes]
               / jnp.maximum(tau0.astype(jnp.float32), 1.0))
    params = jax.jit(partial(_legacy_params, cfg=cfg))(pg, vd=vd,
                                                       btilde0=btilde0)
    n0 = epoch_length(1, base=cfg.n0_base, exponent=cfg.n0_exponent)
    n_nodes = pg.n_nodes

    def epoch_step(g, params, agg_counts, agg_tau, frame_counts,
                   frame_tau, sur_counts, sur_tau, k):
        pspec = jax.tree.map(lambda _: rep, params)

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(gspec, pspec, rep, rep, rep, rep, rep, rep,
                           rep),
                 out_specs=(rep,) * 9, check_vma=False)
        def _step(g, params, agg_counts, agg_tau, frame_counts,
                  frame_tau, sur_counts, sur_tau, k):
            agg_counts = agg_counts + frame_counts
            agg_tau = agg_tau + frame_tau
            (c, t), (sc, st) = sample_batch(g, k, n0, batch_size=bsz,
                                            carry=(sur_counts, sur_tau),
                                            return_carry=True,
                                            axis=all_axes)
            new_counts = jnp.zeros(
                (v_pad,), jnp.float32).at[: c.shape[0]].set(c)
            done, mf, mg = check_stop(agg_counts[:n_nodes], agg_tau,
                                      params)
            return (agg_counts, agg_tau, new_counts, t, sc, st,
                    done, mf, mg)

        return _step(g, params, agg_counts, agg_tau, frame_counts,
                     frame_tau, sur_counts, sur_tau, k)

    epoch_jit = jax.jit(epoch_step)
    agg, frame = zero_frame(v_pad), zero_frame(v_pad)
    sur_counts, sur_tau = jnp.zeros((v1,), jnp.float32), jnp.int32(0)
    done, epoch, k = False, 0, key
    while not done and epoch < cfg.max_epochs:
        k, ke = jax.random.split(k)
        ac, at, fc, ft, sur_counts, sur_tau, done_dev, mf, mg = epoch_jit(
            pg, params, agg.counts, agg.tau, frame.counts, frame.tau,
            sur_counts, sur_tau, ke)
        agg, frame = StateFrame(ac, at), StateFrame(fc, ft)
        done = bool(done_dev)
        epoch += 1
    agg = agg + frame
    agg = StateFrame(agg.counts.at[:v1].add(sur_counts),
                     agg.tau + sur_tau)
    tau = int(agg.tau)
    btilde = np.asarray(agg.counts[: pg.n_nodes]) / max(tau, 1)
    return btilde, tau, epoch, bool(done), float(params.omega), vd
"""

_legacy = {}
exec(compile(_LEGACY_SRC, "<frozen-legacy-drivers>", "exec"), _legacy)


# ---------------------------------------------------------------------------
# Legacy parity: single lane (in-process)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make_graph", [
    lambda: erdos_renyi_graph(200, 6.0, seed=3),
    lambda: grid_graph(16, 12),
], ids=["erdos_renyi", "grid"])
def test_run_kadabra_bit_matches_frozen_legacy_single(make_graph):
    g = make_graph()
    cfg = AdaptiveConfig(eps=0.05, delta=0.1)
    key = jax.random.PRNGKey(7)
    res = run_kadabra(g, key=key, config=cfg)
    bt, tau, ep, conv, omega, vd = _legacy["legacy_run_single"](g, cfg, key)
    np.testing.assert_array_equal(res.btilde, bt)
    assert (res.tau, res.n_epochs, res.converged) == (tau, ep, conv)
    assert res.omega == omega and res.vertex_diameter == vd
    # the wrapper maps the engine's per-metric stats back to scalars
    assert len(res.stats) == res.n_epochs
    assert isinstance(res.stats[0].max_f, float)


def test_run_fixed_sampling_bit_matches_frozen_legacy():
    g = erdos_renyi_graph(150, 5.0, seed=2)
    key = jax.random.PRNGKey(4)
    for bsz in (None, 1, 8):
        new = run_fixed_sampling(g, 96, key=key, batch_size=bsz)
        old = _legacy["legacy_run_fixed"](g, 96, key=key, batch_size=bsz)
        np.testing.assert_array_equal(new, old)


# ---------------------------------------------------------------------------
# Legacy parity: SPMD + sharded lanes (8 fake devices, subprocess)
# ---------------------------------------------------------------------------

_MESH_BODY = r"""
from repro.core import AdaptiveConfig, erdos_renyi_graph, partition_graph, \
    run_kadabra

g = erdos_renyi_graph(96, 5.0, seed=5)
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
key = jax.random.PRNGKey(11)
for agg in ["hierarchical", "flat", "root"]:
    cfg = AdaptiveConfig(eps=0.08, delta=0.1, aggregation=agg, n0_base=400)
    res = run_kadabra(g, mesh=mesh, config=cfg, key=key)
    bt, tau, ep, conv, omega, vd = legacy_run_spmd(g, cfg, key, mesh)
    np.testing.assert_array_equal(res.btilde, bt)
    assert (res.tau, res.n_epochs, res.converged) == (tau, ep, conv), agg
    assert res.omega == omega and res.vertex_diameter == vd
    print("OK spmd", agg)

pg = partition_graph(g, 8)
cfg = AdaptiveConfig(eps=0.08, delta=0.1, n0_base=400)
res = run_kadabra(pg, mesh=mesh, config=cfg, key=key)
bt, tau, ep, conv, omega, vd = legacy_run_sharded(pg, cfg, key, mesh)
np.testing.assert_array_equal(res.btilde, bt)
assert (res.tau, res.n_epochs, res.converged) == (tau, ep, conv)
print("OK sharded")
"""


def test_spmd_and_sharded_lanes_bit_match_frozen_legacy_8dev():
    """Plugin engine vs frozen inline drivers on a 2x2x2 mesh (all three
    aggregations) and on the vertex-sharded cooperative lane.  Subprocess
    because the fake-device flag must precede JAX init."""
    script = ('import os\nos.environ["XLA_FLAGS"] = '
              '"--xla_force_host_platform_device_count=8"\n'
              + _LEGACY_SRC + _MESH_BODY)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert out.stdout.count("OK") == 4


_FIXED_MESH_SCRIPT = r"""
import os
import sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
from repro.core import erdos_renyi_graph, partition_graph, run_fixed
from repro.core.engine import draw_fold
from repro.core.estimators import get_estimator
from repro.core.estimators.base import RunContext

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
g = erdos_renyi_graph(96, 5.0, seed=5)
key = jax.random.PRNGKey(4)
n, bsz = 100, 8
ests = (get_estimator("betweenness"),)
ctx = RunContext(g.n_nodes, 0)


def drawn(k, m):
    c, t = jax.jit(lambda k: draw_fold(g, k, m, estimators=ests, ctx=ctx,
                                       stream="bidir", batch_size=bsz))(k)
    return np.asarray(c), int(t)


if sys.argv[1] == "spmd":
    # every device draws ceil(n / 8) at its own key, then one sum (the
    # counts are whole numbers, so any order of the sum is exact)
    parts = [drawn(k, -(-n // 8)) for k in jax.random.split(key, 8)]
    counts = sum(c for c, _ in parts)
    tau = sum(t for _, t in parts)
    graph = g
else:
    # the mesh draws n cooperatively at the one key: the draw of one
    # device holding the whole graph
    counts, tau = drawn(key, n)
    graph = partition_graph(g, 8)
(rep,) = run_fixed(graph, n, key=key, mesh=mesh, batch_size=bsz)
assert rep.tau == tau, (rep.tau, tau)
np.testing.assert_array_equal(rep.scores, counts[0, : g.n_nodes] / tau)
print("OK")
"""


@pytest.mark.parametrize("lane", ["spmd", "sharded"])
def test_run_fixed_mesh_lanes_match_draw_fold_oracle_8dev(lane):
    """run_fixed draws through the lane's own draw program: on the SPMD
    lane the per-device draws of ceil(n / n_dev) samples summed, on the
    sharded lane the n samples one device would draw."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _FIXED_MESH_SCRIPT, lane],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert out.stdout.count("OK") == 1


# ---------------------------------------------------------------------------
# Closeness / harmonic vs dense oracles
# ---------------------------------------------------------------------------

def _dense_distances(g):
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import shortest_path
    n = g.n_nodes
    nnz = int(np.asarray(g.indptr)[-1])
    adj = csr_matrix((np.ones(nnz, np.int8), np.asarray(g.indices)[:nnz],
                      np.asarray(g.indptr)), shape=(n, n))
    return shortest_path(adj, method="D", unweighted=True)


def _connected_er(n=120, deg=6.0, seed=1):
    for s in range(seed, seed + 20):
        g = erdos_renyi_graph(n, deg, seed=s)
        if np.isfinite(_dense_distances(g)).all():
            return g
    raise RuntimeError("no connected instance found")


def test_closeness_harmonic_match_scipy_oracle():
    from repro.core import run_adaptive
    g = _connected_er()
    n = g.n_nodes
    d = _dense_distances(g)
    res = run_adaptive(g, ("closeness", "harmonic"), eps=0.03, delta=0.1,
                       key=jax.random.PRNGKey(0))
    by_name = {r.name: r for r in res.reports}
    # oracle closeness: (n-1) / sum_s d(s, v)
    exact_clo = (n - 1) / d.sum(axis=0)
    clo = by_name["closeness"]
    assert clo.converged
    # the estimate targets the per-vertex mean of d/cap within eps;
    # propagated through 1/farness that is a relative-error bound
    rel = np.abs(clo.scores - exact_clo) / exact_clo
    assert rel.max() < 0.15, rel.max()
    assert np.corrcoef(clo.scores, exact_clo)[0, 1] > 0.99
    # the cap comes from the phase-1 diameter estimate and must bound
    # the true eccentricities (else min(d, cap) would truncate)
    assert clo.extras["distance_cap"] >= d.max()
    # oracle harmonic (normalized): sum_s 1/d(s, v) / (n-1)
    dh = d.copy()
    np.fill_diagonal(dh, np.inf)
    exact_har = (1.0 / dh).sum(axis=0) / (n - 1)
    har = by_name["harmonic"]
    assert har.converged
    assert np.abs(har.scores - exact_har).max() < 2 * 0.03
    assert np.corrcoef(har.scores, exact_har)[0, 1] > 0.99
    # Hoeffding cap: omega = 0.5/eps^2 ln(2n/delta), shared stop family
    from repro.core.estimators.closeness import hoeffding_omega
    assert har.omega == pytest.approx(float(hoeffding_omega(n, 0.03, 0.1)))


def test_multi_metric_bit_matches_solo_runs():
    """The amortized stack must not perturb any member metric: each
    report is bit-equal to the same metric run alone on the forward
    stream at the same key, even when stopping epochs stagger."""
    from repro.core import run_adaptive
    g = erdos_renyi_graph(150, 6.0, seed=4)
    key = jax.random.PRNGKey(3)
    metrics = ("betweenness", "closeness", "harmonic")
    multi = run_adaptive(g, metrics, eps=0.05, delta=0.1, key=key,
                         stream="forward")
    assert tuple(r.name for r in multi.reports) == metrics
    stop_epochs = set()
    for rep in multi.reports:
        solo = run_adaptive(g, (rep.name,), eps=0.05, delta=0.1, key=key,
                            stream="forward").reports[0]
        np.testing.assert_array_equal(rep.scores, solo.scores)
        assert rep.tau == solo.tau and rep.omega == solo.omega
        stop_epochs.add(rep.stop_epoch)
    # union stopping: the run ends at the LAST metric's stop epoch
    assert multi.n_epochs == max(r.stop_epoch for r in multi.reports)
    assert multi.converged


def test_multi_metric_epoch_lowers_one_bfs_stream():
    """HLO while-instruction census: folding three estimators instead of
    one adds ZERO while loops (= zero traversals) to the jitted draw —
    the amortization is structural, not statistical."""
    import re
    from repro.core.engine import draw_fold
    from repro.core.estimators import get_estimator
    from repro.core.estimators.base import RunContext
    g = erdos_renyi_graph(64, 4.0, seed=0)
    ctx = RunContext(g.n_nodes, 6)

    def n_while(est_names):
        ests = tuple(get_estimator(m) for m in est_names)
        fn = jax.jit(lambda k: draw_fold(g, k, 4, estimators=ests,
                                         ctx=ctx, stream="forward",
                                         batch_size=2))
        hlo = fn.lower(jax.random.PRNGKey(0)).compile().as_text()
        return len(re.findall(r"=\s*\S+\s+while\(", hlo))

    solo = n_while(("betweenness",))
    stack = n_while(("betweenness", "closeness", "harmonic"))
    assert solo >= 1
    assert stack == solo, (solo, stack)


def test_run_fixed_multi_metric_reports():
    from repro.core import run_fixed
    g = erdos_renyi_graph(100, 5.0, seed=6)
    reports = run_fixed(g, 64, metrics=("closeness", "harmonic"),
                        key=jax.random.PRNGKey(1))
    assert [r.name for r in reports] == ["closeness", "harmonic"]
    for r in reports:
        assert r.tau == 64 and not r.converged
        assert np.isfinite(r.scores).all()
        assert r.scores.shape == (g.n_nodes,)


# ---------------------------------------------------------------------------
# Registry + stop-rule dispatch
# ---------------------------------------------------------------------------

def test_registry_surface():
    from repro.core import available_metrics, get_estimator
    names = available_metrics()
    assert {"betweenness", "closeness", "harmonic"} <= set(names)
    # historical alias
    assert type(get_estimator("kadabra")) is type(
        get_estimator("betweenness"))
    with pytest.raises(KeyError, match="betweenness"):
        get_estimator("pagerank")


def test_stop_rule_registry_conflict_is_loud():
    from repro.kernels.stopcheck.ops import (get_stop_rule,
                                             register_stop_rule,
                                             stop_rule_names)
    assert "bernstein" in stop_rule_names()
    fn = get_stop_rule("bernstein")
    register_stop_rule("bernstein", fn)  # idempotent re-register is fine
    with pytest.raises(ValueError, match="bernstein"):
        register_stop_rule("bernstein", lambda *a: a)


# ---------------------------------------------------------------------------
# Checkpoint schema guard
# ---------------------------------------------------------------------------

def test_pre_refactor_checkpoint_fails_loudly(tmp_path):
    """A PR 1-6 checkpoint (7-leaf state, no schema stamp) restored by
    the plugin engine must raise CheckpointSchemaError BEFORE any shape
    assert — and a wrong stamp likewise."""
    import json
    import jax.numpy as jnp
    from repro.checkpoint.store import CheckpointSchemaError, save
    from repro.core.engine import _pad_len
    g = erdos_renyi_graph(80, 5.0, seed=0)
    v_pad = _pad_len(g.n_nodes, 1)
    # the legacy 7-leaf tuple, exactly as the old _EpochCheckpointer
    # wrote it: (agg c, agg tau, frame c, frame tau, sur c, sur tau, key)
    legacy_state = (jnp.zeros((v_pad,)), jnp.int32(0),
                    jnp.zeros((v_pad,)), jnp.int32(0),
                    jnp.zeros((g.n_nodes + 1,)), jnp.int32(0),
                    jax.random.PRNGKey(0))
    ck = tmp_path / "legacy"
    save(str(ck), 1, legacy_state,
         metadata={"epoch": 1, "done": False})  # unstamped: pre-schema
    with pytest.raises(CheckpointSchemaError, match="no schema stamp"):
        run_kadabra(g, eps=0.2, delta=0.1, checkpoint_dir=str(ck))
    # wrong stamp (e.g. a different metric set) is equally loud
    part = run_kadabra(
        g, eps=0.2, delta=0.1, key=jax.random.PRNGKey(0),
        config=AdaptiveConfig(eps=0.2, delta=0.1, max_epochs=1),
        checkpoint_dir=str(tmp_path / "stamped"))
    assert not part.converged
    step_dir = sorted((tmp_path / "stamped").glob("step_*"))[-1]
    mf = step_dir / "manifest.json"
    m = json.loads(mf.read_text())
    assert m["schema"].startswith("epoch-state-v2:betweenness")
    m["schema"] = "epoch-state-v2:closeness[dist_sum,reached]"
    mf.write_text(json.dumps(m))
    with pytest.raises(CheckpointSchemaError, match="is stamped"):
        run_kadabra(g, eps=0.2, delta=0.1,
                    checkpoint_dir=str(tmp_path / "stamped"))


def test_multi_metric_checkpoint_resume_bit_identical(tmp_path):
    """Interrupted multi-metric run resumes to the exact uninterrupted
    result — including frozen per-metric deciding snapshots."""
    import dataclasses
    from repro.core import run_adaptive
    g = erdos_renyi_graph(100, 5.0, seed=8)
    key = jax.random.PRNGKey(2)
    metrics = ("closeness", "harmonic")
    cfg = AdaptiveConfig(eps=0.05, delta=0.1)
    base = run_adaptive(g, metrics, key=key, config=cfg)
    assert base.n_epochs >= 2
    ck = str(tmp_path / "ck")
    part = run_adaptive(g, metrics, key=key,
                        config=dataclasses.replace(cfg, max_epochs=1),
                        checkpoint_dir=ck)
    assert not part.converged
    resumed = run_adaptive(g, metrics, key=key, config=cfg,
                           checkpoint_dir=ck)
    assert resumed.converged and resumed.tau == base.tau
    for rb, rr in zip(base.reports, resumed.reports):
        np.testing.assert_array_equal(rb.scores, rr.scores)
        assert (rb.tau, rb.stop_epoch) == (rr.tau, rr.stop_epoch)
