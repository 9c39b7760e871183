#!/usr/bin/env python
"""Chip smoke: adaptive betweenness (``run_adaptive``) end to end on TPU.

    python chip_smoke.py              # one chip: exact, road, rmat
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

Everything runs in this one process, and only on a TPU: with no TPU the
script exits non-zero before any phase.  Phases (one chip):

* exact: a 2000-vertex hyperbolic graph (examples/quickstart.py's
  instance) on the flat Pallas kernel, checked against exact Brandes;
* road: a 512 x 512 grid (2^18 vertices) with a node-blocked CSC layout
  on the node-blocked kernel, checked bitwise against the same run on
  the XLA reference lane (and one batched BFS, 64 levels deep, compared
  the same way);
* rmat: Graph500-parameter R-MAT, scale 18, edge factor 16, on whatever
  lane the dispatcher picks, checked against a run at a second seed.

``--chips 4`` runs only the mesh phase: a 256 x 256 grid on the SPMD
lane (replicated graph on a 4-device mesh) and the sharded lane
(``partition_graph(g, 4)``), checked against the one-chip run and a
bitwise sampler comparison.

Each phase prints its route, wall and compile time, peak device memory,
samples and epochs, and its check; a failed check or an error fails the
run.  The last line of a passing run is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
Per-phase records also go to ``chiprun_out/chip_smoke.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

# eps per phase: the (eps, delta) guarantee each run is asked for, set so
# the whole script takes a few minutes on one v5e chip
EPS = {"exact": 0.05, "road": 0.3, "rmat": 0.1, "mesh": 0.3}
DELTA = 0.1
ROAD_SIDE = 512
# The road runs' sample width and epoch length.  The automatic width is 8
# on this grid, but on a TPU a (V, B) state array pads B to 128 lanes,
# so a BFS level costs as much at B = 8 as at 64 and B = 64 needs an
# eighth of the rounds; one 64-sample epoch keeps each run to a few
# rounds of ~1000 levels.
ROAD_BATCH, ROAD_N0 = 64, 64
# The mesh phase's grid: three runs of the 512 x 512 road graph cost
# ~10 minutes of four chips; a quarter of the vertices and half the
# diameter cost about an eighth of that
MESH_SIDE = 256
RMAT_SCALE, RMAT_EDGE_FACTOR = 18, 16

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_seconds = [0.0]


def _on_duration(event, duration, **_):
    if event in _COMPILE_EVENTS:
        _compile_seconds[0] += duration


class CheckFailed(Exception):
    pass


def require(ok, what):
    if not ok:
        raise CheckFailed(what)


def _max_abs(a, b):
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _run_summary(res):
    return {"route": res.route, "batch_size": res.batch_size,
            "tau": res.tau, "epochs": res.n_epochs,
            "converged": res.converged,
            "vertex_diameter": res.vertex_diameter,
            "phase_seconds": {k: round(v, 3)
                              for k, v in res.phase_seconds.items()}}


# ---------------------------------------------------------------------------
# Phases: each returns a dict of what it measured and checked
# ---------------------------------------------------------------------------

def phase_exact(n=2000, eps=EPS["exact"]):
    import jax
    from repro.core import brandes_numpy, hyperbolic_graph, run_adaptive
    g = hyperbolic_graph(n, avg_degree=12.0, seed=0)
    res = run_adaptive(g, eps=eps, delta=DELTA, key=jax.random.PRNGKey(0))
    err = _max_abs(res.reports[0].scores, brandes_numpy(g))
    require(res.route == "flat", f"route {res.route!r} != 'flat'")
    require(res.converged, "run did not converge")
    require(err < eps, f"max |b~ - b| = {err} >= eps = {eps}")
    return {"graph": f"hyperbolic n={n} avg_degree=12", "eps": eps,
            "run": _run_summary(res), "max_abs_err_vs_brandes": err,
            "check": f"route flat; converged; max |b~ - b| = {err:.6f} "
                     f"< eps = {eps}"}


def _road(side):
    """The grid, the same grid with a CSC layout blocked for the road
    runs' sample width, and the config of those runs."""
    from repro.core import AdaptiveConfig, grid_graph, with_csc_layout
    g = grid_graph(side, side)
    cfg = AdaptiveConfig(sample_batch_size=ROAD_BATCH, n0_base=ROAD_N0)
    return g, with_csc_layout(g, batch=ROAD_BATCH), cfg


def phase_road(side=ROAD_SIDE, eps=EPS["road"]):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import bfs_sssp_batched, run_adaptive
    g, gc, cfg = _road(side)
    bsz = cfg.sample_batch_size
    # one batched BFS per lane, each search stopped at a vertex 64 hops
    # away: the compiled kernel's dist/sigma against the XLA reference's,
    # bit for bit, with sigma far past bfloat16's 2^8 (and float32's 2^24)
    rng = np.random.default_rng(0)
    r, c = rng.integers(0, side - 32, bsz), rng.integers(0, side - 32, bsz)
    src = jnp.asarray(r * side + c, jnp.int32)
    stop = jnp.asarray((r + 32) * side + c + 32, jnp.int32)
    bfs = jax.jit(bfs_sssp_batched)
    b_nb, b_ref = bfs(gc, src, stop_nodes=stop), bfs(g, src, stop_nodes=stop)
    v1 = g.n_nodes + 1
    sigma_nb, sigma_ref = (np.asarray(b_nb.sigma)[:v1],
                           np.asarray(b_ref.sigma))
    bfs_equal = (np.array_equal(np.asarray(b_nb.dist)[:v1],
                                np.asarray(b_ref.dist))
                 and np.array_equal(sigma_nb, sigma_ref))
    max_sigma = float(sigma_ref.max())
    require(bfs_equal, "batched BFS dist/sigma differ between lanes")
    require(max_sigma > 2.0 ** 24, f"max sigma {max_sigma} <= 2^24")

    key = jax.random.PRNGKey(0)
    r_nb = run_adaptive(gc, eps=eps, delta=DELTA, key=key, config=cfg)
    r_ref = run_adaptive(g, eps=eps, delta=DELTA, key=key, config=cfg)
    s_nb, s_ref = r_nb.reports[0].scores, r_ref.reports[0].scores
    equal = (np.array_equal(np.asarray(s_nb), np.asarray(s_ref))
             and r_nb.tau == r_ref.tau)
    require(r_nb.route == "node_blocked",
            f"route {r_nb.route!r} != 'node_blocked'")
    require(r_ref.route == "ref", f"reference route {r_ref.route!r}")
    require(r_nb.converged and r_ref.converged, "run did not converge")
    require(equal, f"scores/tau differ from the reference lane "
                   f"(max diff {_max_abs(s_nb, s_ref)}, tau {r_nb.tau} vs "
                   f"{r_ref.tau})")
    return {"graph": f"grid {side}x{side}", "eps": eps,
            "csc": {"block_v": gc.csc.block_v, "block_e": gc.csc.block_e,
                    "edge_slots": gc.csc.e_slots, "edges": g.n_edges},
            "run": _run_summary(r_nb), "reference_run": _run_summary(r_ref),
            "bfs_max_sigma": max_sigma,
            "check": f"route node_blocked; BFS dist/sigma bitwise equal to "
                     f"ref (max sigma {max_sigma:.3e}); scores and tau "
                     f"bitwise equal to the ref run (tau {r_nb.tau})"}


def phase_rmat(scale=RMAT_SCALE, edge_factor=RMAT_EDGE_FACTOR,
               eps=EPS["rmat"]):
    import jax
    from repro.core import rmat_graph, run_adaptive
    g = rmat_graph(scale, edge_factor, seed=0)
    r1 = run_adaptive(g, eps=eps, delta=DELTA, key=jax.random.PRNGKey(1))
    r2 = run_adaptive(g, eps=eps, delta=DELTA, key=jax.random.PRNGKey(2))
    diff = _max_abs(r1.reports[0].scores, r2.reports[0].scores)
    require(r1.converged and r2.converged, "run did not converge")
    require(diff <= 2 * eps, f"seeds differ by {diff} > 2 eps")
    return {"graph": f"rmat scale={scale} edge_factor={edge_factor} "
                     f"(|E| undirected {g.n_edges_undirected})",
            "eps": eps, "run": _run_summary(r1),
            "second_seed_run": _run_summary(r2),
            "max_abs_diff_between_seeds": diff,
            "check": f"converged at both seeds; max |b~1 - b~2| = "
                     f"{diff:.6f} <= 2 eps = {2 * eps}"}


def phase_mesh(side=MESH_SIDE, eps=EPS["mesh"], n_dev=4):
    from functools import partial

    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import partition_graph, run_adaptive, sample_batch
    devices = jax.devices()
    require(len(devices) == n_dev, f"{len(devices)} devices, need {n_dev}")
    g, gc, cfg = _road(side)
    bsz = cfg.sample_batch_size
    axes = ("data",)
    mesh = jax.make_mesh((n_dev,), axes,
                         axis_types=(jax.sharding.AxisType.Auto,))
    pg = partition_graph(g, n_dev, batch=bsz)

    # the cooperative sharded sampler against the single-device one at
    # the same key, bit for bit
    key = jax.random.PRNGKey(5)
    n_samp = bsz

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(pg.partition_spec(axes),
                                                 P()),
             out_specs=(P(), P()), check_vma=False)
    def sharded_draw(pgl, k):
        return sample_batch(pgl, k, n_samp, batch_size=bsz, axis=axes)

    c_sh, t_sh = sharded_draw(pg, key)
    c_one, t_one = jax.jit(partial(sample_batch, n_samples=n_samp,
                                   batch_size=bsz))(g, key)
    draw_equal = (np.array_equal(np.asarray(c_sh), np.asarray(c_one))
                  and int(t_sh) == int(t_one) == n_samp)
    require(draw_equal, "sharded sample_batch differs from single-device")

    key = jax.random.PRNGKey(0)
    one = run_adaptive(gc, eps=eps, delta=DELTA, key=key, config=cfg)
    spmd = run_adaptive(gc, mesh=mesh, eps=eps, delta=DELTA, key=key,
                        config=cfg)
    shard = run_adaptive(pg, mesh=mesh, eps=eps, delta=DELTA, key=key,
                         config=cfg)
    s_one = one.reports[0].scores
    d_spmd = _max_abs(spmd.reports[0].scores, s_one)
    d_shard = _max_abs(shard.reports[0].scores, s_one)
    in_use = _memory(devices, "bytes_in_use")
    require(one.route == "node_blocked" and spmd.route == "node_blocked",
            f"routes {one.route!r}, {spmd.route!r}")
    require(shard.route == "sharded_nb", f"sharded route {shard.route!r}")
    require(one.converged and spmd.converged and shard.converged,
            "run did not converge")
    require(d_spmd <= 2 * eps and d_shard <= 2 * eps,
            f"lanes differ from one chip: spmd {d_spmd}, sharded {d_shard}")
    return {"graph": f"grid {side}x{side}", "eps": eps,
            "one_chip": _run_summary(one), "spmd": _run_summary(spmd),
            "sharded": _run_summary(shard),
            "bytes_in_use_per_device": in_use,
            "max_abs_diff_spmd": d_spmd, "max_abs_diff_sharded": d_shard,
            "check": f"sharded sample_batch bitwise equal to single-device "
                     f"({n_samp} samples); max |spmd - one| = "
                     f"{d_spmd:.6f}, max |sharded - one| = {d_shard:.6f} "
                     f"<= 2 eps = {2 * eps}; bytes_in_use per device "
                     f"{in_use}"}


PHASES = {"exact": phase_exact, "road": phase_road, "rmat": phase_rmat,
          "mesh": phase_mesh}


def _memory(devices, stat):
    """One ``memory_stats()`` entry per device (None where the backend
    reports none)."""
    return [(d.memory_stats() or {}).get(stat) for d in devices]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform "
              f"{devices[0].platform!r}); nothing run", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    names = ["mesh"] if args.chips == 4 else ["exact", "road", "rmat"]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: {device}; compile cache: {cache}")
    print("eps per phase: " + ", ".join(f"{n}={EPS[n]}" for n in names)
          + f" (delta={DELTA})")

    records, failed = [], []
    for name in names:
        t0, c0 = time.perf_counter(), _compile_seconds[0]
        try:
            rec = PHASES[name]()
            status = "PASS"
        except Exception as e:  # noqa: BLE001 - reported, and fails the run
            traceback.print_exc()
            rec = {"error": f"{type(e).__name__}: {e}"}
            status = "FAIL"
            failed.append(name)
        rec.update(phase=name, status=status,
                   wall_seconds=round(time.perf_counter() - t0, 3),
                   compile_seconds=round(_compile_seconds[0] - c0, 3),
                   peak_bytes_in_use=_memory(devices, "peak_bytes_in_use"))
        records.append(rec)
        run = rec.get("run") or rec.get("one_chip") or {}
        print(f"[{name}] {status} route={run.get('route')} "
              f"B={run.get('batch_size')} wall={rec['wall_seconds']}s "
              f"compile={rec['compile_seconds']}s "
              f"peak_bytes_in_use={rec['peak_bytes_in_use']} "
              f"tau={run.get('tau')} epochs={run.get('epochs')}")
        print(f"[{name}] check: {rec.get('check', rec.get('error'))}")
        print(f"[{name}] record: {json.dumps(rec, sort_keys=True)}",
              flush=True)

    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump({"device": device, "phases": records}, f, indent=1)
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
