"""Benchmark harness — one function per paper table/figure.

Outputs ``name,us_per_call,derived`` CSV rows (plus a human-readable
report).  Laptop-scale stand-ins for the paper's instances:

  table2   Per-instance adaptive-sampling statistics (paper Table II):
           epochs, samples, time, per-epoch aggregate volume, on a
           road-like grid, an R-MAT social-like graph and a random
           hyperbolic graph.
  fig2     Phase breakdown (diameter / calibration / sampling) and the
           aggregation-mode comparison (hierarchical vs flat vs
           reduce-to-root) — paper Fig. 2b + §IV-E/F.
  fig3     Sampling throughput (samples/s) single-device and the
           per-epoch sample growth across mesh sizes (paper Fig. 3).
           NOTE: this container has ONE physical core — fake devices
           serialize, so multi-device rows report *work structure*
           (samples/epoch, epochs) rather than wall-clock speedup; the
           roofline report covers projected parallel behavior.
  fig4     Adaptive-sampling time vs graph size on R-MAT and hyperbolic
           graphs (paper Fig. 4), laptop scales.
  node_blocked_sweep
           Frontier-lane throughput (flat Pallas vs node-blocked CSC
           Pallas vs XLA ref) at V in {2^12, 2^15, 2^17} — the two-level
           kernel's scaling story past the flat kernel's VMEM cap.
  csc_driver_sweep
           Occupancy-skipping work efficiency on a high-diameter grid
           at V=2^15: per-BFS-level skipped-block ratios and the
           skip/no-skip speedup of the node-blocked kernel (the
           O(frontier)-blocks-per-level story of the CSC BFS driver).
  partition_sweep
           Replicated vs vertex-sharded frontier lane at V in
           {2^15, 2^17} on an 8-fake-device mesh (subprocess):
           per-device frontier-lane graph bytes (asserted at
           <= (1/n_dev + eps) of the replicated CSCLayout), per-level
           frontier-exchange volume, and samples/s of the independent
           vs cooperative sampling lanes.
  metric_sweep
           Multi-estimator amortization: samples/s of one forward draw
           stream folding betweenness+closeness+harmonic together vs
           three independent single-metric streams (each on its natural
           stream).  The committed row asserts the >=1.5x amortization
           acceptance of the estimator substrate; ``--smoke`` runs a
           seconds-scale version for CI.
  kernels  Pallas-kernel oracle microbenches (XLA path timings; the
           Pallas path is interpret-mode on CPU and not timed).

``python -m benchmarks.run`` runs everything at quick settings;
``--full`` enlarges instances.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

CSV_ROWS = []


def emit(name: str, us_per_call: float, derived: str = ""):
    CSV_ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def _time_call(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def _fake_device_env():
    """Environment of a child process that forces fake host devices: it
    runs on the CPU, so it never reaches for a chip this process holds."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    return env


# Fields every BENCH_sampling.json row must carry (each sweep includes
# its sweep-specific payload beside them).
_BENCH_REQUIRED_FIELDS = ("section", "timestamp", "mode")


def record_run(record: dict, out_path: str = None):
    """Append one validated run record to BENCH_sampling.json.

    The one shared append helper (every sweep routes through here).
    Rows are schema-checked first — ``section``/``timestamp``/``mode``
    must be present — so a malformed row fails its own run instead of
    poisoning the history.  An unreadable existing history file is
    *preserved*: it is renamed to ``BENCH_sampling.json.bak`` (never
    silently discarded — quick runs must not clobber committed --full
    baselines, and a corrupt file is still evidence) and a fresh
    history is started.
    """
    import json
    missing = [f for f in _BENCH_REQUIRED_FIELDS if f not in record]
    if missing:
        raise ValueError(
            f"bench record (section={record.get('section')!r}) is missing "
            f"required fields {missing}; every row carries "
            f"{list(_BENCH_REQUIRED_FIELDS)}")
    if out_path is None:
        out_path = os.path.join(os.path.dirname(__file__), "..",
                                "BENCH_sampling.json")
    history = {"runs": []}
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                prev = json.load(f)
        except (json.JSONDecodeError, OSError):
            bak = out_path + ".bak"
            os.replace(out_path, bak)
            print(f"  WARNING: unreadable {os.path.abspath(out_path)} "
                  f"backed up to {os.path.abspath(bak)}; starting a "
                  f"fresh history")
            prev = None
        if isinstance(prev, dict):
            # single-record legacy format (no "runs") is itself a run
            prev = prev.get("runs", [prev])
        if isinstance(prev, list):
            history["runs"] = prev
    history["runs"].append(record)
    with open(out_path, "w") as f:
        json.dump(history, f, indent=1)
    print(f"  appended run #{len(history['runs'])} to "
          f"{os.path.abspath(out_path)}")


# Backwards-compatible alias (pre-PR 9 name used by older scripts).
_append_bench_record = record_run


# ---------------------------------------------------------------------------
# Table II analogue
# ---------------------------------------------------------------------------

def bench_table2(full: bool):
    from repro.core import (AdaptiveConfig, grid_graph, hyperbolic_graph,
                            rmat_graph, run_kadabra)
    scale = 12 if full else 10
    instances = [
        ("grid-road", grid_graph(48 if full else 24, 32 if full else 16)),
        ("rmat-social", rmat_graph(scale, 8, seed=1)),
        ("hyperbolic", hyperbolic_graph(1 << (scale - 1), 12.0, seed=2)),
    ]
    print("\n== Table II analogue: per-instance adaptive-sampling stats ==")
    print(f"{'instance':<14}{'|V|':>8}{'|E|':>9}{'Ep.':>5}{'Samples':>9}"
          f"{'Com. MiB/ep':>12}{'Time s':>8}")
    for name, g in instances:
        cfg = AdaptiveConfig(eps=0.05, delta=0.1, n0_base=400)
        t0 = time.perf_counter()
        res = run_kadabra(g, config=cfg, key=jax.random.PRNGKey(0))
        dt = time.perf_counter() - t0
        com_mib = (g.n_nodes + 1) * 4 / 2**20  # one frame per epoch
        print(f"{name:<14}{g.n_nodes:>8}{g.n_edges_undirected:>9}"
              f"{res.n_epochs:>5}{res.tau:>9}{com_mib:>12.2f}"
              f"{res.phase_seconds['sampling']:>8.2f}")
        emit(f"table2.{name}", dt * 1e6,
             f"epochs={res.n_epochs};samples={res.tau};"
             f"omega={res.omega:.0f};converged={res.converged}")


# ---------------------------------------------------------------------------
# Fig 2 analogue: phases + aggregation modes
# ---------------------------------------------------------------------------

_AGG_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time, jax
from repro.core import AdaptiveConfig, rmat_graph, run_kadabra
g = rmat_graph(9, 8, seed=1)
for agg in ["hierarchical", "flat", "root"]:
    cfg = AdaptiveConfig(eps=0.08, delta=0.1, aggregation=agg, n0_base=400)
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    t0 = time.perf_counter()
    res = run_kadabra(g, mesh=mesh, config=cfg, key=jax.random.PRNGKey(0))
    print(f"AGG {agg} {time.perf_counter()-t0:.3f} {res.tau} {res.n_epochs}")
"""


def bench_fig2(full: bool):
    from repro.core import AdaptiveConfig, rmat_graph, run_kadabra
    g = rmat_graph(11 if full else 9, 8, seed=1)
    cfg = AdaptiveConfig(eps=0.05, delta=0.1, n0_base=400)
    res = run_kadabra(g, config=cfg, key=jax.random.PRNGKey(0))
    total = sum(res.phase_seconds.values())
    print("\n== Fig 2b analogue: phase breakdown (single device) ==")
    for phase, sec in res.phase_seconds.items():
        print(f"  {phase:<12} {sec:7.2f}s  ({100*sec/max(total,1e-9):4.1f}%)")
        emit(f"fig2.phase.{phase}", sec * 1e6,
             f"pct={100*sec/max(total,1e-9):.1f}")

    print("\n== §IV-E/F analogue: aggregation modes on a 2x2x2 mesh ==")
    env = _fake_device_env()
    out = subprocess.run([sys.executable, "-c", _AGG_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    if out.returncode:
        print("  subprocess failed:", out.stderr[-400:])
        return
    for line in out.stdout.splitlines():
        if line.startswith("AGG"):
            _tag, agg, sec, tau, ep = line.split()
            print(f"  {agg:<13} {float(sec):6.2f}s  tau={tau} epochs={ep}")
            emit(f"fig2.agg.{agg}", float(sec) * 1e6,
                 f"tau={tau};epochs={ep}")


# ---------------------------------------------------------------------------
# Fig 3 analogue: sampling throughput + epoch structure
# ---------------------------------------------------------------------------

def bench_fig3(full: bool):
    from repro.core import rmat_graph
    from repro.core.sampler import sample_batch
    from repro.core.epoch import epoch_length
    from repro.core.adaptive import resolve_sample_batch_size
    from repro.core.diameter import estimate_diameter
    g = rmat_graph(11 if full else 9, 8, seed=3)
    n = 64
    # the lane run_kadabra actually executes on this instance: B is
    # resolved from the phase-1 diameter estimate, exactly as the driver
    # does (B=16 was the old fixed default; R-MAT resolves wider)
    vd = int(jax.jit(estimate_diameter)(g).vertex_diameter)
    B = resolve_sample_batch_size(None, g.n_nodes, vd)
    fn = jax.jit(lambda k: sample_batch(g, k, n, batch_size=B))
    us = _time_call(fn, jax.random.PRNGKey(0))
    rate = n / (us / 1e6)
    print(f"\n== Fig 3 analogue: sampling throughput ==")
    print(f"  single device (B={B}): {rate:,.0f} samples/s "
          f"(|V|={g.n_nodes}, |E|={g.n_edges_undirected})")
    emit("fig3.samples_per_s", us / n, f"rate={rate:.0f};batch={B}")
    print("  epoch length schedule n0 = 1000/(PT)^1.33 (paper §IV-D):")
    for devs in [1, 8, 64, 256, 512]:
        print(f"    devices={devs:<5} n0/device={epoch_length(devs):>5} "
              f"samples/epoch={devs * epoch_length(devs):>6}")


# ---------------------------------------------------------------------------
# Fig 4 analogue: scaling with graph size
# ---------------------------------------------------------------------------

def bench_fig4(full: bool):
    from repro.core import AdaptiveConfig, hyperbolic_graph, rmat_graph, \
        run_kadabra
    scales = [8, 9, 10, 11] if full else [8, 9, 10]
    print("\n== Fig 4 analogue: adaptive sampling time vs graph size ==")
    for fam, make in [("rmat", lambda s: rmat_graph(s, 8, seed=s)),
                      ("hyperbolic",
                       lambda s: hyperbolic_graph(1 << (s - 1), 12.0,
                                                  seed=s))]:
        for s in scales:
            g = make(s)
            cfg = AdaptiveConfig(eps=0.08, delta=0.1, n0_base=400)
            res = run_kadabra(g, config=cfg, key=jax.random.PRNGKey(1))
            samp = res.phase_seconds["sampling"]
            per_v = samp / g.n_nodes * 1e6
            print(f"  {fam:<11} |V|={g.n_nodes:<7} |E|="
                  f"{g.n_edges_undirected:<8} sampling={samp:6.2f}s "
                  f"({per_v:.2f} us/vertex)")
            emit(f"fig4.{fam}.s{s}", samp * 1e6,
                 f"V={g.n_nodes};us_per_vertex={per_v:.2f}")


# ---------------------------------------------------------------------------
# Batch sweep: samples/s vs concurrent-sample count B
# ---------------------------------------------------------------------------

def bench_batch_sweep(full: bool):
    """Throughput of the batched sampling lane at B in {1, 4, 16, 64} on
    the R-MAT laptop-scale instance.  B concurrent samples share one edge
    stream per BFS level (SpMV -> SpMM), so samples/s should grow until
    the relaxation turns compute-bound.  Results also land in
    BENCH_sampling.json so later PRs have a trajectory to compare
    against."""
    from repro.core import rmat_graph
    from repro.core.sampler import sample_batch
    g = rmat_graph(11 if full else 9, 8, seed=3)
    n = 512 if full else 256
    print("\n== batch sweep: samples/s vs batch size B ==")
    print(f"  instance: R-MAT |V|={g.n_nodes} |E|={g.n_edges_undirected}, "
          f"{n} samples per measurement")
    rows = []
    base_rate = None
    for B in [1, 4, 16, 64]:
        fn = jax.jit(lambda k, B=B: sample_batch(g, k, n, batch_size=B))
        us = _time_call(fn, jax.random.PRNGKey(0))
        rate = n / (us / 1e6)
        base_rate = base_rate or rate
        print(f"  B={B:<4} {rate:>12,.0f} samples/s   "
              f"(speedup vs B=1: {rate / base_rate:4.2f}x)")
        emit(f"batch_sweep.B{B}", us / n, f"rate={rate:.0f};"
             f"speedup={rate / base_rate:.2f}")
        rows.append({"batch_size": B, "samples_per_s": rate,
                     "us_per_sample": us / n,
                     "speedup_vs_b1": rate / base_rate})
    _append_bench_record({
        "section": "batch_sweep",
        "mode": "xla",
        "instance": {"family": "rmat", "n_nodes": g.n_nodes,
                     "n_edges_undirected": g.n_edges_undirected,
                     "edge_factor": 8, "seed": 3},
        "n_samples_per_measurement": n,
        "full": full,
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
        "device": jax.devices()[0].platform,
        "results": rows,
    })


# ---------------------------------------------------------------------------
# Node-blocked sweep: frontier-kernel throughput vs graph size V
# ---------------------------------------------------------------------------

def bench_node_blocked_sweep(full: bool, interpret: bool = True):
    """Per-level sampling throughput of the three frontier lanes (flat
    Pallas, node-blocked CSC Pallas, XLA ref) at V in {2^12, 2^15, 2^17}.

    One frontier expansion advances B concurrent samples by one BFS
    level, so samples/s here = B / t_expand — the per-level throughput
    (divide by the instance's mean search depth for end-to-end
    samples/s; the ratio BETWEEN lanes is depth-independent).  At
    V = 2^17 the flat kernel's (V+1) * B state is rejected by
    ``pallas_supported`` — only the node-blocked lane (and the XLA ref)
    can run, which is the regime the two-level kernel exists for.  The
    instances are 2D grids (the paper's road-network stand-in): the
    staged gather's pair-bucketed layout is sized for source locality,
    and a scattered Erdos-Renyi instance at 2^17 would pay ~100x slot
    padding (DESIGN.md §Perf "Staged gather").  ``interpret`` selects
    the Pallas execution mode (``--interpret``/``--compiled``;
    compiled requires real TPU hardware) and is recorded per row as
    ``pallas_mode``, so interpret-mode rates are never silently
    compared against hardware runs; interpret-mode absolute rates
    understate a real TPU massively, but the node-blocked / flat ratio
    is still meaningful (the two-level kernel does (V+1)/block_v fewer
    one-hot MACs per edge).  Results append to BENCH_sampling.json so
    the perf trajectory stays machine-readable.
    """
    from repro.core import build_csc_layout, grid_graph
    from repro.core.bfs import bfs_sssp_batched
    from repro.kernels.frontier import (frontier_expand_batched_pallas,
                                        frontier_expand_batched_ref,
                                        frontier_expand_node_blocked_pallas,
                                        pallas_supported)
    B = 8
    reps = 3 if full else 1
    mode = "interpret" if interpret else "compiled"
    print("\n== node-blocked sweep: frontier lanes vs graph size ==")
    print(f"  B={B} concurrent samples; samples/s = per-level throughput; "
          f"pallas_mode={mode}")
    rows = []
    for scale in [12, 15, 17]:
        v = 1 << scale
        g = grid_graph(1 << ((scale + 1) // 2), 1 << (scale // 2))
        csc = build_csc_layout(g)
        rng = np.random.default_rng(scale)
        sources = jnp.asarray(rng.integers(0, v, B), jnp.int32)
        res = jax.jit(bfs_sssp_batched)(g, sources)
        dist, sigma = res.dist, res.sigma
        levels = jnp.full((B,), 2, jnp.int32)
        # eligibility: the flat kernel's all-resident (V+1, B) state
        flat_ok = pallas_supported(g.n_nodes, batch=B)
        lanes = {
            "xla_ref": jax.jit(lambda d, s: frontier_expand_batched_ref(
                g.src, g.dst, d, s, levels)),
            "node_blocked": jax.jit(
                lambda d, s: frontier_expand_node_blocked_pallas(
                    csc, d, s, levels, interpret=interpret)),
        }
        if flat_ok:
            lanes["flat"] = jax.jit(
                lambda d, s: frontier_expand_batched_pallas(
                    g.src, g.dst, d, s, levels, interpret=interpret))
        row = {"scale": scale, "n_nodes": v,
               "n_edges_directed": int(g.n_edges),
               "flat_supported": bool(flat_ok),
               "block_v": csc.block_v, "block_e": csc.block_e,
               "batch": B, "pallas_mode": mode, "lanes": {}}
        for name, fn in lanes.items():
            us = _time_call(fn, dist, sigma, reps=reps)
            rate = B / (us / 1e6)
            row["lanes"][name] = {"us_per_expand": us, "samples_per_s": rate}
            print(f"  V=2^{scale:<3} {name:<13} {us:>12,.0f} us/expand "
                  f"{rate:>12,.1f} samples/s"
                  + ("" if flat_ok or name != "node_blocked"
                     else "   (flat kernel rejected: V*B over VMEM budget)"))
            emit(f"node_blocked_sweep.s{scale}.{name}", us,
                 f"samples_per_s={rate:.1f};flat_supported={flat_ok}")
        if flat_ok:
            ratio = (row["lanes"]["node_blocked"]["samples_per_s"]
                     / row["lanes"]["flat"]["samples_per_s"])
            row["node_blocked_vs_flat"] = ratio
            print(f"           node_blocked/flat throughput: {ratio:.2f}x")
        rows.append(row)
    _append_bench_record({
        "section": "node_blocked_sweep",
        "mode": mode,
        "instance": {"family": "grid"},
        "metric": "samples_per_s = B / t(one frontier expansion); "
                  "per-BFS-level throughput",
        "pallas_mode": mode,
        "full": full,
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
        "device": jax.devices()[0].platform,
        "results": rows,
    })


# ---------------------------------------------------------------------------
# CSC driver sweep: occupancy skipping on a high-diameter grid
# ---------------------------------------------------------------------------

def run_csc_driver_sweep(scale: int = 15, batch: int = 8, reps: int = 1,
                         probe_levels=None, write_json: bool = True,
                         full: bool = False):
    """Level-throughput of the CSC frontier lanes on a high-diameter grid.

    The workload occupancy skipping exists for: a 2^scale-vertex 2D grid
    (the paper's road-network stand-in), BFS states taken from a real
    corner-seeded search, so the frontier at level L is the genuine
    anti-diagonal — O(L) vertices out of 2^scale, touching O(L / rows)
    of the edge blocks.  Per probed level the node-blocked kernel runs
    twice — occupancy bitmap on vs forced all-ones — plus the XLA ref;
    recorded per level: the skipped-block ratio and the skip/no-skip
    speedup.  Both kernel lanes are interpret-mode on CPU, so absolute
    rates understate real hardware, but the skip/no-skip ratio is the
    work-efficiency measurement itself (identical kernel, identical
    schedule, only inactive grid cells differ).  Returns the result
    rows; ``write_json`` appends them to BENCH_sampling.json.
    """
    from repro.core import grid_graph, with_csc_layout
    from repro.core.bfs import bfs_sssp_batched
    from repro.kernels.frontier import (frontier_block_bitmap,
                                        frontier_expand_batched_ref,
                                        frontier_expand_node_blocked_pallas)
    width = 1 << ((scale + 1) // 2)
    height = 1 << (scale // 2)
    g = grid_graph(width, height)
    gc = with_csc_layout(g, batch=batch)
    csc = gc.csc
    # corner-seeded searches: the deepest frontiers a grid offers
    sources = jnp.zeros((batch,), jnp.int32)
    res = jax.jit(bfs_sssp_batched)(gc, sources)
    dist, sigma = res.dist, res.sigma
    depth = int(res.levels[0])
    if probe_levels is None:
        probe_levels = sorted({1, 2, depth // 8, depth // 4, depth // 2,
                               depth - 2} - {0})
    print(f"\n== csc_driver_sweep: occupancy skipping, grid "
          f"{width}x{height} (V=2^{scale}) ==")
    print(f"  B={batch}, blocks (v={csc.block_v}, e={csc.block_e}), "
          f"{csc.n_edge_blocks} edge blocks, depth={depth}")
    # mean occupancy over the whole search (bitmap only — cheap)
    bitmap_fn = jax.jit(lambda d, lv: frontier_block_bitmap(csc, d, lv))
    occ = []
    for lv in range(depth):
        lvv = jnp.full((batch,), lv, jnp.int32)
        occ.append(int(jnp.sum(bitmap_fn(dist, lvv))))
    mean_active = float(np.mean(occ))
    print(f"  mean active edge blocks over {depth} levels: "
          f"{mean_active:.1f} / {csc.n_edge_blocks} "
          f"(mean skipped ratio {1 - mean_active / csc.n_edge_blocks:.3f})")

    skip_fn = jax.jit(lambda d, s, lv: frontier_expand_node_blocked_pallas(
        csc, d, s, lv, skip_inactive=True))
    noskip_fn = jax.jit(lambda d, s, lv: frontier_expand_node_blocked_pallas(
        csc, d, s, lv, skip_inactive=False))
    ref_fn = jax.jit(lambda d, s, lv: frontier_expand_batched_ref(
        g.src, g.dst, d, s, lv))
    # warm the allocator/dispatch path beyond the compile call — the very
    # first executed call otherwise pollutes the first probed level
    warm = jnp.full((batch,), int(probe_levels[0]), jnp.int32)
    for fn in (skip_fn, noskip_fn, ref_fn):
        jax.block_until_ready(fn(dist, sigma, warm))
    rows = []
    tot_skip = tot_noskip = 0.0
    for lv in probe_levels:
        lvv = jnp.full((batch,), lv, jnp.int32)
        active = int(jnp.sum(bitmap_fn(dist, lvv)))
        us_skip = _time_call(skip_fn, dist, sigma, lvv, reps=reps)
        us_noskip = _time_call(noskip_fn, dist, sigma, lvv, reps=reps)
        us_ref = _time_call(ref_fn, dist, sigma, lvv, reps=reps)
        speedup = us_noskip / us_skip
        tot_skip += us_skip
        tot_noskip += us_noskip
        skipped = 1 - active / csc.n_edge_blocks
        rows.append({
            "level": lv, "active_blocks": active,
            "n_edge_blocks": csc.n_edge_blocks,
            "skipped_ratio": skipped,
            "us_skip": us_skip, "us_noskip": us_noskip, "us_xla_ref": us_ref,
            "samples_per_s_skip": batch / (us_skip / 1e6),
            "speedup_skip_vs_noskip": speedup,
        })
        print(f"  L={lv:<4} active={active:>4}/{csc.n_edge_blocks} "
              f"skip={us_skip:>10,.0f}us noskip={us_noskip:>10,.0f}us "
              f"ref={us_ref:>8,.0f}us  speedup={speedup:5.2f}x")
        emit(f"csc_driver_sweep.L{lv}", us_skip,
             f"speedup={speedup:.2f};skipped_ratio={skipped:.3f}")
    overall = tot_noskip / max(tot_skip, 1e-9)
    print(f"  aggregate over probed levels: {overall:.2f}x from skipping")
    record = {
        "section": "csc_driver_sweep",
        "mode": "interpret",
        "instance": {"family": "grid", "width": width, "height": height,
                     "n_nodes": g.n_nodes,
                     "n_edges_directed": int(g.n_edges)},
        "blocking": {"block_v": csc.block_v, "block_e": csc.block_e,
                     "n_edge_blocks": csc.n_edge_blocks,
                     "v_pad": csc.v_pad},
        "batch": batch, "bfs_depth": depth,
        "mean_active_blocks": mean_active,
        "metric": "per-level frontier expansion; speedup = t(all-ones "
                  "bitmap) / t(occupancy bitmap), interpret-mode Pallas",
        "aggregate_speedup": overall,
        "full": full,
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
        "device": jax.devices()[0].platform,
        "results": rows,
    }
    if write_json:
        _append_bench_record(record)
    return record


def bench_csc_driver_sweep(full: bool):
    run_csc_driver_sweep(scale=15, batch=8, reps=3 if full else 1,
                         full=full)


# ---------------------------------------------------------------------------
# Partition sweep: replicated vs vertex-sharded frontier lane
# ---------------------------------------------------------------------------

_PARTITION_SCRIPT = r"""
import os, json, sys, time
args = json.loads(os.environ.get("PARTITION_SWEEP_ARGS", "{}"))
n_dev = int(args.get("n_dev", 8))
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={n_dev}")
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.core import (build_csc_layout, erdos_renyi_graph, exchange_plan,
                        grid_graph, max_active_source_chunks,
                        partition_graph)
from repro.core.bfs import bfs_sssp_batched
from repro.core.sampler import sample_batch

B = int(args.get("batch", 8))
n = int(args.get("n_samples", 16))
reps = int(args.get("reps", 1))
mesh = jax.make_mesh((n_dev,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
axes = ("data",)

def timeit(fn, *a):
    # compile + warm; block so the async warmup dispatch cannot leak
    # into the timed window (worst at reps=1)
    jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps

instances = ([("erdos_renyi", s) for s in args.get("scales", [15, 17])]
             + [("grid", s) for s in args.get("grid_scales", [])])
for family, scale in instances:
    v = 1 << scale
    if family == "grid":
        # high-diameter road-network-like instance (narrow grid,
        # diameter ~V/8): frontiers span O(1) source blocks per level —
        # the regime the sparse exchange protocol targets
        g = grid_graph(v // 8, 8)
    else:
        g = erdos_renyi_graph(v, 4.0, seed=scale)
    csc = build_csc_layout(g, batch=B)
    pg = partition_graph(g, n_dev, batch=B)
    # --- per-device graph bytes: the frontier-lane edge structure ------
    rep_bytes = sum(int(np.asarray(a).nbytes) for a in
                    (csc.src, csc.dst, csc.block_nb, csc.block_sb,
                     csc.block_first))
    tot_shard = sum(int(np.asarray(a).nbytes) for a in
                    (pg.shards.src, pg.shards.dst, pg.shards.block_nb,
                     pg.shards.block_sb, pg.shards.block_first))
    per_dev = tot_shard // n_dev
    # acceptance: per-device shard bytes <= (1/n_dev + eps) * replicated
    # (eps covers the per-bucket block padding of small shards)
    assert per_dev <= rep_bytes * (1.0 / n_dev + 0.20), (per_dev, rep_bytes)
    # --- per-level frontier-exchange volume (real BFS trace) -----------
    rng = np.random.default_rng(scale)
    sources = jnp.asarray(rng.integers(0, v, B), jnp.int32)
    res = jax.jit(bfs_sssp_batched)(g, sources)
    dist = np.asarray(res.dist)
    depth = int(np.asarray(res.levels).max())
    # per level: which protocol the bitmap-scheduled exchange takes
    # (sparse when the worst shard's active source blocks fit the static
    # budget, dense fallback otherwise) and the bytes it moves, from the
    # shared ExchangePlan accounting; masked_frontier_bytes stays the
    # LOGICAL frontier volume (the unpadded lower bound)
    plan = exchange_plan(pg, B)
    levels = []
    exchange_total = dense_total = 0
    for lv in range(depth + 1):
        mask = (dist == lv).any(axis=1)
        rows = int(mask.sum())
        mab = max_active_source_chunks(pg, mask)
        lv_bytes = plan.level_bytes(mab)
        exchange_total += lv_bytes
        dense_total += plan.dense_bytes
        levels.append({"level": lv, "frontier_rows": rows,
                       "masked_frontier_bytes": rows * B * 4,
                       "active_chunks_max_per_shard": mab,
                       "sparse_taken": plan.sparse_taken(mab),
                       "exchange_bytes": lv_bytes,
                       "dense_gather_bytes": plan.dense_bytes})
    # --- samples/s: replicated independent vs sharded cooperative ------
    gspec = pg.partition_spec(axes)
    rep_gspec = jax.tree.map(lambda _: P(), g)

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(gspec, P()),
             out_specs=(P(), P()), check_vma=False)
    def shard_samp(pgl, k):
        return sample_batch(pgl, k, n, batch_size=B, axis=axes)

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(rep_gspec, P("data")),
             out_specs=(P("data"), P("data")), check_vma=False)
    def rep_samp(gl, ks):
        c, t = sample_batch(gl, ks[0], n, batch_size=B)
        return c[None], t.reshape(1)

    key = jax.random.PRNGKey(scale)
    t_shard = timeit(shard_samp, pg, key)
    t_rep = timeit(rep_samp, g, jax.random.split(key, n_dev))
    row = {
        "family": family, "scale": scale, "n_nodes": int(g.n_nodes),
        "n_edges_directed": int(g.n_edges),
        "pallas_mode": args.get("pallas_mode", "interpret"),
        "n_dev": n_dev, "batch": B, "n_samples": n,
        "blocking": {"block_v": pg.shards.block_v,
                     "block_e": pg.shards.block_e,
                     "shard_rows": pg.shard_rows, "v_pad": pg.v_pad},
        "replicated_csc_bytes": rep_bytes,
        "per_device_shard_bytes": per_dev,
        "bytes_ratio": per_dev / rep_bytes,
        "exchange_budget_blocks": plan.budget,
        "dense_gather_bytes_per_level": plan.dense_bytes,
        "sparse_protocol_bytes_per_level": plan.sparse_bytes,
        "exchange_bytes_total": exchange_total,
        "dense_bytes_total": dense_total,
        "exchange_vs_dense_ratio": exchange_total / dense_total,
        "bfs_depth": depth,
        "exchange_per_level": levels,
        "samples_per_s_sharded": n / t_shard,
        "samples_per_s_replicated_total": n_dev * n / t_rep,
        "seconds_sharded": t_shard, "seconds_replicated": t_rep,
    }
    print("ROW " + json.dumps(row), flush=True)
print("PARTITION SWEEP OK")
"""


def run_partition_sweep(scales, n_dev: int = 8, batch: int = 8,
                        n_samples: int = 16, reps: int = 1,
                        write_json: bool = True, full: bool = False,
                        grid_scales=(), interpret: bool = True):
    """Replicated vs vertex-sharded frontier lane (subprocess: the fake
    device count must be set before JAX initializes).

    Measures, per instance (Erdos-Renyi per ``scales`` entry, plus a
    high-diameter grid per ``grid_scales`` entry — the regime the
    sparse exchange targets): (i) the per-device frontier-lane graph
    bytes — the acceptance claim of the partitioning subsystem,
    asserted inside the script at <= (1/n_dev + eps) of the replicated
    CSCLayout; (ii) the per-level volume of the bitmap-scheduled
    frontier exchange (DESIGN.md §Frontier exchange): which protocol
    each level takes (sparse when the worst shard's active source
    blocks fit the partition's static budget, dense fallback
    otherwise), exchange_bytes vs the dense baseline, and the
    exchange_vs_dense_ratio aggregate — masked_frontier_bytes stays
    the logical rows * B * 4 lower bound; (iii) samples/s of the
    replicated independent lane (n_dev * n samples) vs the sharded
    cooperative lane (n samples, the whole mesh on one batch).  On
    this container fake devices serialize, so the sharded lane's
    absolute rate understates real hardware, but the memory + exchange
    columns are exact.  ``interpret`` names the Pallas execution mode
    the sweep's expansions run under (``--interpret``/``--compiled``)
    and is recorded per row as ``pallas_mode``, so interpret-mode rates
    are never silently compared against hardware runs.  Returns the
    rows; ``write_json`` appends to BENCH_sampling.json."""
    import json
    env = _fake_device_env()
    env["PARTITION_SWEEP_ARGS"] = json.dumps({
        "scales": list(scales), "grid_scales": list(grid_scales),
        "n_dev": n_dev, "batch": batch,
        "n_samples": n_samples, "reps": reps,
        "pallas_mode": "interpret" if interpret else "compiled"})
    out = subprocess.run([sys.executable, "-c", _PARTITION_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=3600)
    if out.returncode or "PARTITION SWEEP OK" not in out.stdout:
        raise RuntimeError(f"partition sweep subprocess failed:\n"
                           f"stdout:{out.stdout[-2000:]}\n"
                           f"stderr:{out.stderr[-2000:]}")
    rows = [json.loads(line[4:]) for line in out.stdout.splitlines()
            if line.startswith("ROW ")]
    for row in rows:
        n_sparse = sum(lv["sparse_taken"] for lv in row["exchange_per_level"])
        print(f"  {row['family'][:4]:>4} V=2^{row['scale']:<3} "
              f"shard/replicated bytes "
              f"{row['bytes_ratio']:.3f} (1/n_dev={1/row['n_dev']:.3f})  "
              f"exchange/dense {row['exchange_vs_dense_ratio']:.3f} "
              f"({n_sparse}/{len(row['exchange_per_level'])} levels "
              f"sparse, K={row['exchange_budget_blocks']})  "
              f"sharded {row['samples_per_s_sharded']:,.1f} samples/s vs "
              f"replicated {row['samples_per_s_replicated_total']:,.1f} "
              f"(x{row['n_dev']} devices)")
        emit(f"partition_sweep.{row['family']}.s{row['scale']}.sharded",
             row["seconds_sharded"] * 1e6 / row["n_samples"],
             f"bytes_ratio={row['bytes_ratio']:.3f};"
             f"exchange_ratio={row['exchange_vs_dense_ratio']:.3f};"
             f"samples_per_s={row['samples_per_s_sharded']:.1f}")
    record = {
        "section": "partition_sweep",
        "mode": "interpret" if interpret else "compiled",
        "instance": {"families": ["erdos_renyi", "grid"],
                     "avg_degree_er": 4.0},
        "pallas_mode": "interpret" if interpret else "compiled",
        "metric": "per-device frontier-lane bytes (sharded vs replicated "
                  "CSCLayout); per-level bitmap-scheduled exchange: "
                  "exchange_bytes = protocol actually taken (sparse when "
                  "active blocks fit the budget, dense fallback "
                  "otherwise), masked_frontier_bytes = logical frontier "
                  "lower bound; samples/s "
                  "replicated-independent vs sharded-cooperative; fake "
                  "devices serialize",
        "full": full,
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
        "device": "cpu",
        "results": rows,
    }
    if write_json:
        # deep-BFS instances carry thousands of per-level entries; the
        # committed history keeps aggregates exact and subsamples the
        # per-level trace to a bounded stride (the returned rows stay
        # complete for in-process consumers)
        slim_rows = []
        for row in rows:
            lv = row["exchange_per_level"]
            stride = max(1, -(-len(lv) // 512))
            if stride > 1:
                row = {**row, "exchange_per_level": lv[::stride],
                       "exchange_per_level_stride": stride}
            slim_rows.append(row)
        _append_bench_record({**record, "results": slim_rows})
    return record


def bench_partition_sweep(full: bool, interpret: bool = True):
    print("\n== partition sweep: replicated vs vertex-sharded lane ==")
    # the scattered Erdos-Renyi instance stays at 2^15: at 2^17 the
    # pair-bucketed staged-gather layout pays ~100x slot padding on a
    # scattered graph (DESIGN.md §Perf "Staged gather"); the 2^17 point
    # runs on the high-diameter grid, the regime the layout targets
    run_partition_sweep([15], grid_scales=[15, 17], n_dev=8, batch=8,
                        n_samples=32 if full else 16,
                        reps=3 if full else 1, full=full,
                        interpret=interpret)


# ---------------------------------------------------------------------------
# Metric sweep: multi-estimator amortization over one BFS stream
# ---------------------------------------------------------------------------

def run_metric_sweep(scale: int = 9, n_samples: int = 256, reps: int = 3,
                     smoke: bool = False, write_json: bool = True,
                     full: bool = False):
    """Samples/s of the shared-stream multi-estimator fold vs three
    independent single-metric streams.

    The estimator substrate's amortization claim: a
    betweenness+closeness+harmonic stack folds all four channels out of
    ONE forward BFS stream per drawn sample (dryrun's ``while_loops``
    census shows the identical traversal count), so serving E metrics
    costs one traversal instead of E.  Here that is measured end-to-end:
    ``draw_fold`` with the 3-estimator stack, timed against the sum of
    the three solo streams — each solo on its NATURAL stream
    (betweenness on the cheaper bidirectional draw, the distance metrics
    on forward), so the baseline is what three separate runs would
    actually cost, not a strawman.  Amortization = t(3 solo) / t(multi);
    the committed (non-smoke) row asserts >= 1.5x.  ``--smoke`` shrinks
    the instance to a seconds-scale CI gate that checks the lane runs
    and the stack agrees with the solo streams on tau.
    """
    from repro.core import rmat_graph
    from repro.core.diameter import estimate_diameter
    from repro.core.engine import draw_fold, resolve_sample_batch_size
    from repro.core.estimators import get_estimator
    from repro.core.estimators.base import RunContext

    if smoke:
        scale, n, reps = 8, 64, 1
    else:
        n = 512 if full else n_samples
    g = rmat_graph(scale, 8, seed=3)
    vd = int(jax.jit(estimate_diameter)(g).vertex_diameter)
    ctx = RunContext(g.n_nodes, vd)
    B = resolve_sample_batch_size(None, g.n_nodes, vd)
    metrics = ("betweenness", "closeness", "harmonic")
    ests = {m: get_estimator(m) for m in metrics}
    print("\n== metric sweep: shared-stream amortization =="
          + ("  [smoke]" if smoke else ""))
    print(f"  instance: R-MAT |V|={g.n_nodes} |E|={g.n_edges_undirected}, "
          f"{n} samples, B={B}, vd={vd}")

    def lane(est_stack, stream):
        return jax.jit(lambda k: draw_fold(
            g, k, n, estimators=est_stack, ctx=ctx, stream=stream,
            batch_size=B))

    key = jax.random.PRNGKey(0)
    us_multi = _time_call(lane(tuple(ests.values()), "forward"), key,
                          reps=reps)
    solo_us = {}
    for m, e in ests.items():
        stream = "forward" if e.needs_forward else "bidir"
        solo_us[m] = _time_call(lane((e,), stream), key, reps=reps)
        print(f"  solo {m:<12} ({stream:>7}) "
              f"{n / (solo_us[m] / 1e6):>12,.0f} samples/s")
    us_indep = sum(solo_us.values())
    amort = us_indep / us_multi
    rate_multi = len(metrics) * n / (us_multi / 1e6)
    print(f"  multi (3 metrics, forward) "
          f"{rate_multi:>12,.0f} metric-samples/s")
    print(f"  amortization vs three independent runs: {amort:.2f}x"
          + ("" if smoke else "  (acceptance: >= 1.5x)"))
    # tau agreement: the stack consumed exactly the solo sample count
    _, tau_multi = lane(tuple(ests.values()), "forward")(key)
    assert int(tau_multi) == n, (int(tau_multi), n)
    if not smoke:
        assert amort >= 1.5, f"amortization {amort:.2f}x below 1.5x"
    emit("metric_sweep.multi", us_multi / n,
         f"amortization={amort:.2f};metric_samples_per_s={rate_multi:.0f}")
    for m in metrics:
        emit(f"metric_sweep.solo.{m}", solo_us[m] / n,
             f"rate={n / (solo_us[m] / 1e6):.0f}")
    record = {
        "section": "metric_sweep",
        "mode": "xla",
        "instance": {"family": "rmat", "n_nodes": g.n_nodes,
                     "n_edges_undirected": g.n_edges_undirected,
                     "edge_factor": 8, "seed": 3},
        "metrics": list(metrics),
        "n_samples": n, "batch_size": B, "smoke": smoke,
        "metric": "amortization = sum(t solo streams, each on its "
                  "natural stream) / t(one forward stream folding all "
                  "channels); acceptance >= 1.5x on the committed row",
        "us_per_sample_multi": us_multi / n,
        "us_per_sample_solo": {m: solo_us[m] / n for m in metrics},
        "metric_samples_per_s_multi": rate_multi,
        "amortization_vs_independent": amort,
        "full": full,
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
        "device": jax.devices()[0].platform,
    }
    if write_json and not smoke:
        _append_bench_record(record)
    return record


def bench_metric_sweep(full: bool, smoke: bool = False):
    run_metric_sweep(reps=3 if full else 2, smoke=smoke, full=full)


# ---------------------------------------------------------------------------
# Weighted sweep: delta-stepping bucket structure vs BFS levels
# ---------------------------------------------------------------------------

def run_weighted_sweep(smoke: bool = False, write_json: bool = True,
                       full: bool = False, reps: int = 2):
    """Round structure and throughput of the weighted lane vs BFS.

    Delta-stepping's work-efficiency story is its ROUND structure: on a
    road-like grid (bounded degree, weights clustered around the mean)
    the average-weight delta heuristic settles each source in a handful
    of bucket advances, while a hop-synchronous traversal pays one round
    per BFS level; on a skewed (heavy-tailed, R-MAT) weight profile the
    windows fragment and the bucket count grows toward the weighted
    depth.  Both regimes are recorded side by side: per-source mean
    bucket advances and weighted DAG depth from
    :class:`repro.core.bfs.SSSPResult` against the BFS level count of
    the SAME topology, plus us/source for each driver.  ``--smoke`` is
    the seconds-scale CI gate (tiny instances, no BENCH row); the
    reachability cross-check — weighted and unweighted traversals reach
    the same vertex set — runs in every mode.
    """
    from repro.core import (grid_graph, rmat_graph,
                            symmetric_dyadic_weights, with_weights)
    from repro.core.bfs import bfs_sssp_batched, delta_sssp_batched

    rng = np.random.default_rng(23)

    def skewed_weights(g, seed):
        # heavy-tailed power-of-two dyadic weights 2^k/16, k in [0, 8),
        # symmetric per undirected pair, exactly representable in f32
        wrng = np.random.default_rng(seed)
        srcs = np.asarray(g.src[: g.n_edges])
        dsts = np.asarray(g.dst[: g.n_edges])
        pairs = np.unique(np.stack([np.minimum(srcs, dsts),
                                    np.maximum(srcs, dsts)], 1), axis=0)
        draws = wrng.integers(0, 8, len(pairs))
        wmap = {tuple(p): float(2 ** k) / 16.0
                for p, k in zip(pairs, draws)}
        return np.array([wmap[(min(a, b), max(a, b))]
                         for a, b in zip(srcs, dsts)], np.float32)

    if smoke:
        B = 8
        grid = grid_graph(16, 12)
        rmat = rmat_graph(7, 8, seed=3)
    else:
        B = 32
        grid = grid_graph(96, 64) if full else grid_graph(48, 32)
        rmat = rmat_graph(12 if full else 10, 8, seed=3)
    cases = [
        ("grid_uniform", grid,
         with_weights(grid, symmetric_dyadic_weights(grid, seed=5))),
        ("rmat_skewed", rmat, with_weights(rmat, skewed_weights(rmat, 7))),
    ]
    print("\n== weighted sweep: delta-stepping buckets vs BFS levels =="
          + ("  [smoke]" if smoke else ""))
    rows = []
    for name, base, g in cases:
        sources = jnp.asarray(rng.integers(0, g.n_nodes, B), jnp.int32)
        wfn = jax.jit(delta_sssp_batched)
        bfn = jax.jit(bfs_sssp_batched)
        wres = wfn(g, sources)
        bres = bfn(base, sources)
        # same topology => same reachable set, float vs int sentinels
        wreach = np.asarray(wres.dist) >= 0.0
        breach = np.asarray(bres.dist) >= 0
        assert (wreach == breach).all(), name
        us_w = _time_call(wfn, g, sources, reps=reps)
        us_b = _time_call(bfn, base, sources, reps=reps)
        buckets = float(np.asarray(wres.buckets).mean())
        wdepth = float(np.asarray(wres.levels).mean())
        blevels = float(np.asarray(bres.levels).mean())
        print(f"  {name:<14} |V|={g.n_nodes:>6} buckets/src={buckets:7.1f} "
              f"wdepth/src={wdepth:7.1f} bfs_levels/src={blevels:7.1f} "
              f"us/src w={us_w / B:9.1f} bfs={us_b / B:9.1f}")
        emit(f"weighted_sweep.{name}", us_w / B,
             f"buckets={buckets:.1f};bfs_levels={blevels:.1f}")
        rows.append({
            "family": name, "n_nodes": g.n_nodes,
            "n_edges_undirected": g.n_edges_undirected, "batch": B,
            "mean_buckets_per_source": buckets,
            "mean_weighted_depth_per_source": wdepth,
            "mean_bfs_levels_per_source": blevels,
            "us_per_source_weighted": us_w / B,
            "us_per_source_bfs": us_b / B,
        })
    record = {
        "section": "weighted_sweep",
        "mode": "xla",
        "metric": "per-source bucket advances (delta-stepping, "
                  "average-weight delta) and weighted DAG depth vs BFS "
                  "level count of the same topology; us/source for both "
                  "drivers",
        "results": rows, "smoke": smoke, "full": full,
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
        "device": jax.devices()[0].platform,
    }
    if write_json and not smoke:
        _append_bench_record(record)
    return record


def bench_weighted_sweep(full: bool, smoke: bool = False):
    run_weighted_sweep(smoke=smoke, full=full, reps=3 if full else 2)


# ---------------------------------------------------------------------------
# Fault matrix: resilience sweep over the injected-failure taxonomy
# ---------------------------------------------------------------------------

_FAULT_MATRIX_SCRIPT = r"""
import json, os, tempfile, time
args = json.loads(os.environ["FAULT_MATRIX_ARGS"])
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%d"
                           % args["n_dev"])
import numpy as np
import jax
from jax.sharding import Mesh
from repro.core.graph import build_graph
from repro.core.partition import partition_graph
from repro.core.adaptive import AdaptiveConfig
from repro.core.engine import run_adaptive
from repro.core.brandes import brandes_numpy
from repro.runtime import (ResilientRunner, FaultSchedule, FaultSpec,
                           RetryPolicy, read_jsonl)

V = args["n_nodes"]
n_dev = args["n_dev"]
rng = np.random.default_rng(0)
src = rng.integers(0, V, 4 * V)
dst = (src + 1 + rng.integers(0, V - 1, 4 * V)) % V
g = build_graph(np.concatenate([src, dst]), np.concatenate([dst, src]), V)
pg = partition_graph(g, n_dev)
mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("dev",))
cfg = AdaptiveConfig(eps=args["eps"], delta=0.1, max_epochs=24)
exact = brandes_numpy(g)
key = jax.random.PRNGKey(11)
policy = RetryPolicy(max_retries=8, backoff_base=1e-3, backoff_cap=1e-3)

baselines = {}
def baseline(lane):
    # the uninterrupted run every bit-identity cell is judged against
    if lane not in baselines:
        r = (run_adaptive(pg, ("betweenness",), mesh=mesh, config=cfg,
                          key=key) if lane == "sharded"
             else run_adaptive(g, ("betweenness",), config=cfg, key=key))
        baselines[lane] = r.reports[0]
    return baselines[lane]

def cell(name, lane, sched, expect, epoch_timeout=None):
    t0 = time.perf_counter()
    graph, m = (pg, mesh) if lane == "sharded" else (g, None)
    with tempfile.TemporaryDirectory() as d:
        trace = os.path.join(d, "trace.jsonl")
        out = ResilientRunner(graph, mesh=m, config=cfg, key=key,
                              checkpoint_dir=d, schedule=sched,
                              policy=policy, telemetry=trace,
                              epoch_timeout=epoch_timeout).run()
        # JSONL round-trip: every line re-validates against the event
        # taxonomy, the supervisor's RunEvents all made it onto the bus,
        # and the trace alone reproduces the run outcome
        evs = read_jsonl(trace, validate=True)
        sup = [e.kind.split(".", 1)[1] for e in evs
               if e.kind.startswith("supervisor.")]
        assert sup == [e.kind for e in out.events], (name, sup)
        ends = [e for e in evs if e.kind == "run.end"]
        assert ends and ends[-1].fields["tau"] == out.result.tau, (name, "tau")
        assert ends[-1].fields["n_epochs"] == out.result.n_epochs, \
            (name, "epochs")
    rep = out.result.reports[0]
    base = baseline(lane)
    bit = bool(np.array_equal(np.asarray(rep.scores),
                              np.asarray(base.scores))
               and rep.tau == base.tau)
    err = float(np.max(np.abs(np.asarray(rep.scores) - exact)))
    taus = [s.tau for s in out.result.stats]
    tau_monotone = all(b >= a for a, b in zip(taus, taus[1:]))
    if expect == "bit":
        assert bit, (name, "expected bit-identical recovery")
    else:
        assert rep.converged and err <= cfg.eps, (name, err, cfg.eps)
        assert tau_monotone, (name, taus)
    row = {"cell": name, "faults": [s.kind for s in sched],
           "lane_start": lane, "lane_final": out.lane,
           "n_dev_final": out.n_devices, "attempts": out.attempts,
           "n_events": len(out.events),
           "event_kinds": sorted({e.kind for e in out.events}),
           "expect": ("bit_identical" if expect == "bit"
                      else "within_eps_exact_tau"),
           "bit_identical": bit, "max_abs_err_vs_exact": err,
           "tau": rep.tau, "tau_trace_monotone": tau_monotone,
           "seconds": time.perf_counter() - t0}
    print("ROW " + json.dumps(row), flush=True)

half = n_dev // 2
# same-mesh faults recover bit-identically; the elastic shrink changes
# the calibration stream, so its contract is (eps, delta) + exact tau
cell("kill", "sharded", FaultSchedule([FaultSpec("kill", 1),
                                       FaultSpec("kill", 2)]), "bit")
cell("nan", "sharded", FaultSchedule([FaultSpec("nan", 2)]), "bit")
cell("shrink", "sharded",
     FaultSchedule([FaultSpec("shrink", 2, survivors=half)]), "eps")
cell("seeded-mix", "single",
     FaultSchedule.from_seed(args["seed"],
                             kinds=("kill", "nan", "corrupt", "truncate",
                                    "hang"),
                             n_faults=4, max_epoch=4, hang_delay=0.01),
     "bit")
if not args["smoke"]:
    cell("corrupt", "sharded", FaultSchedule([FaultSpec("corrupt", 2)]),
         "bit")
    cell("truncate", "sharded",
         FaultSchedule([FaultSpec("truncate", 2)]), "bit")
    cell("hang-timeout", "single",
         FaultSchedule([FaultSpec("hang", 2, delay=0.5)]), "bit",
         epoch_timeout=0.2)
print("FAULT MATRIX OK")
"""


def run_fault_matrix(n_dev: int = 8, smoke: bool = False,
                     write_json: bool = True, full: bool = False,
                     seed: int = 17):
    """Resilience acceptance sweep (subprocess: the fake device count
    must be set before JAX initializes).

    One cell per fault class of ``repro.runtime.faults``, each driving
    a full adaptive betweenness run through ``ResilientRunner`` under a
    seeded schedule and checking the recovery contract: same-mesh
    faults (mid-epoch kill, NaN-poisoned frame, checkpoint corruption,
    torn manifest, hung epoch) must converge **bit-identical** to the
    uninterrupted run at the same key (asserted inside the script);
    the elastic 8→4 shrink re-partitions onto the surviving mesh and
    must converge within (eps, delta) of the exact Brandes scores with
    a monotone tau trace (no discarded in-flight draw ever re-counted).
    The ``seeded-mix`` cell replays a ``FaultSchedule.from_seed``
    multi-fault storm on the single-device lane.  ``--smoke`` is the
    tier-1 CI gate: 4 cells on a smaller instance, no BENCH row.
    """
    import json
    env = _fake_device_env()
    env["FAULT_MATRIX_ARGS"] = json.dumps({
        "n_dev": n_dev, "n_nodes": 120 if smoke else (400 if full else 200),
        "eps": 0.1 if smoke else 0.08, "smoke": smoke, "seed": seed})
    out = subprocess.run([sys.executable, "-c", _FAULT_MATRIX_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=3600)
    if out.returncode or "FAULT MATRIX OK" not in out.stdout:
        raise RuntimeError(f"fault matrix subprocess failed:\n"
                           f"stdout:{out.stdout[-2000:]}\n"
                           f"stderr:{out.stderr[-2000:]}")
    rows = [json.loads(line[4:]) for line in out.stdout.splitlines()
            if line.startswith("ROW ")]
    for row in rows:
        verdict = ("bit-identical" if row["bit_identical"]
                   else f"err={row['max_abs_err_vs_exact']:.4f}")
        print(f"  {row['cell']:<12} [{'+'.join(row['faults']):<24}] "
              f"{row['lane_start']:>7} -> {row['lane_final']}/"
              f"{row['n_dev_final']}dev  attempts={row['attempts']}  "
              f"{verdict}  ({row['seconds']:.1f}s)")
        emit(f"fault_matrix.{row['cell']}", row["seconds"] * 1e6,
             f"attempts={row['attempts']};"
             f"bit_identical={row['bit_identical']};"
             f"err={row['max_abs_err_vs_exact']:.5f}")
    record = {
        "section": "fault_matrix",
        "mode": "xla",
        "n_dev": n_dev, "smoke": smoke, "full": full, "seed": seed,
        "metric": "per fault class: ResilientRunner completes the run; "
                  "same-mesh faults bit-identical to the uninterrupted "
                  "run at the same key; elastic shrink within (eps, "
                  "delta) of exact Brandes with a monotone tau trace "
                  "(exact sample accounting)",
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
        "device": "cpu",
        "results": rows,
    }
    if write_json and not smoke:
        _append_bench_record(record)
    return record


def bench_fault_matrix(full: bool, smoke: bool = False):
    print("\n== fault matrix: resilience under injected failures =="
          + ("  [smoke]" if smoke else ""))
    run_fault_matrix(smoke=smoke, full=full)


# ---------------------------------------------------------------------------
# Kernel microbenches
# ---------------------------------------------------------------------------

def bench_kernels(full: bool):
    from repro.core import erdos_renyi_graph
    from repro.core.bfs import bfs_sssp
    from repro.kernels.frontier import frontier_expand_ref
    from repro.kernels.segsum import gather_segment_sum_ref
    from repro.kernels.stopcheck import stopcheck_ref
    print("\n== kernel oracle timings (XLA path; Pallas = interpret) ==")
    g = erdos_renyi_graph(20000 if full else 5000, 16.0, seed=0)
    res = bfs_sssp(g, 0)
    fe = jax.jit(lambda: frontier_expand_ref(g.src, g.dst, res.dist,
                                             res.sigma, 2))
    us = _time_call(fe)
    emit("kernel.frontier.xla", us, f"edges={g.e_pad}")

    rng = np.random.default_rng(0)
    n, v, d, s = (65536, 4096, 128, 1024) if full else (8192, 1024, 128, 256)
    ids = jnp.asarray(rng.integers(0, v, n), jnp.int32)
    seg = jnp.asarray(rng.integers(0, s, n), jnp.int32)
    w = jnp.ones((n,), jnp.float32)
    table = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    ss = jax.jit(lambda: gather_segment_sum_ref(ids, seg, w, table, s))
    emit("kernel.segsum.xla", _time_call(ss), f"N={n};D={d}")

    vv = 200000 if full else 50000
    counts = jnp.asarray(rng.integers(0, 50, vv), jnp.float32)
    lil = jnp.asarray(rng.random(vv) * 10 + 0.1, jnp.float32)
    sc = jax.jit(lambda: stopcheck_ref(counts, 500, lil, lil, 1e5))
    emit("kernel.stopcheck.xla", _time_call(sc), f"V={vv}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    sections = ["table2", "fig2", "fig3", "fig4", "batch_sweep",
                "node_blocked_sweep", "csc_driver_sweep", "partition_sweep",
                "metric_sweep", "weighted_sweep", "fault_matrix", "kernels"]
    ap.add_argument("section", nargs="?", default=None, choices=sections,
                    help="run a single section (same as --only)")
    ap.add_argument("--only", default=None, choices=sections)
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--interpret", dest="interpret", action="store_true",
                      default=True,
                      help="run Pallas kernels in interpret mode (default; "
                           "the only mode this CPU container can execute)")
    mode.add_argument("--compiled", dest="interpret", action="store_false",
                      help="compile the Pallas kernels (Mosaic; requires "
                           "real TPU hardware) — recorded per "
                           "BENCH_sampling.json row as pallas_mode")
    ap.add_argument("--smoke", action="store_true",
                    help="metric_sweep / weighted_sweep / fault_matrix: "
                         "seconds-scale CI "
                         "gate (tiny instance, fewer cells, no BENCH "
                         "row, no >=1.5x assertion)")
    args = ap.parse_args()
    if args.only and args.section and args.only != args.section:
        ap.error(f"conflicting sections: positional '{args.section}' "
                 f"vs --only '{args.only}'")
    args.only = args.only or args.section
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    jobs = {
        "table2": bench_table2, "fig2": bench_fig2, "fig3": bench_fig3,
        "fig4": bench_fig4, "batch_sweep": bench_batch_sweep,
        "node_blocked_sweep": bench_node_blocked_sweep,
        "csc_driver_sweep": bench_csc_driver_sweep,
        "partition_sweep": bench_partition_sweep,
        "metric_sweep": bench_metric_sweep,
        "weighted_sweep": bench_weighted_sweep,
        "fault_matrix": bench_fault_matrix,
        "kernels": bench_kernels,
    }
    takes_mode = {"node_blocked_sweep", "partition_sweep"}
    takes_smoke = {"metric_sweep", "weighted_sweep", "fault_matrix"}
    for name, fn in jobs.items():
        if args.only and name != args.only:
            continue
        if name in takes_mode:
            fn(args.full, interpret=args.interpret)
        elif name in takes_smoke:
            fn(args.full, smoke=args.smoke)
        else:
            fn(args.full)
    print("\n== CSV summary ==")
    print("name,us_per_call,derived")
    for row in CSV_ROWS:
        print(f"{row[0]},{row[1]:.1f},{row[2]}")


if __name__ == "__main__":
    main()
