"""The road256 deployment: its lattice generator, its configuration and
cell, its bfloat16 control, the frontier roofline counts and the two
per-layer metrics that read the program's BFS work counters."""
from __future__ import annotations

import types

import jax
import numpy as np
import pytest

from bench import control, control_bf16_state, harness, phases, reference
from bench import roofline, tracing


def _lattice(rows, cols):
    """A rows x cols lattice, built here apart from the generator."""
    v = np.arange(rows * cols).reshape(rows, cols)
    return np.concatenate([
        np.stack([v[:, :-1].ravel(), v[:, 1:].ravel()], axis=1),
        np.stack([v[:-1, :].ravel(), v[1:, :].ravel()], axis=1)]), rows * cols


def test_grid_generator_gives_the_road256_lattice():
    edges, n = harness.load_module("generators", "grid").edges(
        {"side": 256}, seed=123)
    rg = reference.build(edges, n)
    assert (n, rg.indices.size) == (65_536, 261_120)
    assert (rg.deg.min(), rg.deg.max()) == (2, 4)
    # a corner's eccentricity is the lattice's hop diameter
    assert reference.bfs_dist(rg, [0]).max() == 510
    assert (reference.bfs_dist(rg, [n - 1]) >= 0).all()   # one component
    want, _ = _lattice(256, 256)
    assert sorted(map(tuple, np.sort(edges, axis=1))) == \
        sorted(map(tuple, np.sort(want, axis=1)))


def test_road256_resolves_to_its_cell():
    cell = harness.resolve_cell(harness.load_spec(), "road256.bc")
    assert cell.chips == 1 and cell.traffic["lane"] == "single"
    assert cell.config["generator"] == "grid"
    assert cell.config["graph"] == {"side": 256}
    assert cell.config["reduced"] == ["side"]
    assert cell.config["adaptive"]["sample_batch_size"] == 64
    assert cell.config["control"] == control_bf16_state.CONTROL
    assert [m["name"] for m in cell.end_to_end] == ["job_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["level_ms",
                                                  "frontier_roofline_share"]
    kron = harness.resolve_cell(harness.load_spec(), "kron18.bc")
    assert {"level_ms", "frontier_roofline_share"} <= \
        {m["name"] for m in kron.per_layer}


def test_bf16_state_fails_len_z_where_the_reference_passes():
    """On a 2 x 600 lattice a third of the pairs lie more than 256 hops
    apart: the bfloat16 state loses their paths, float32 keeps them."""
    edges, n = _lattice(2, 600)
    cell = harness.resolve_cell(harness.load_spec(), "road256.bc")
    config = dict(cell.config, adaptive=dict(cell.config["adaptive"],
                                             n0_base=64))
    limits = cell.config["limits"]
    seed = 2147490401
    exp = harness.expected(config, edges, n, seed)
    bf16 = control_bf16_state.control_job(exp, edges, n, seed)
    plain = control.control_job(exp, edges, n, seed, None)
    for job in (bf16, plain):
        assert job["tau"] == exp.samples_lo == 128
    bad = harness.check.compare(bf16, exp)
    good = harness.check.compare(plain, exp)
    assert bad["len_z"] > limits["len_z"], bad
    assert good["len_z"] <= limits["len_z"], good
    for k in ("tau_gap", "vd_short", "bad_counts"):
        assert bad[k] <= limits[k] and good[k] <= limits[k]


def test_roofline_counts_match_a_hand_count():
    # a 16 x 16 lattice blocked 128 / 128: 3 node blocks (v_pad 384),
    # B = 8; three streamed edge blocks over two expansions
    ops, nbytes = roofline.node_blocked(3, 2, n_nodes=256, batch=8,
                                        block_v=128, block_e=128)
    step_bytes = 2 * 128 * 4 + 2 * 128 * 8 * 4     # ids, dist/sigma tiles
    assert ops == 3 * 2 * (2 * 128 * 128 * 8)      # gather + scatter matmul
    assert nbytes == 3 * step_bytes + 2 * 384 * 8 * 4
    ops, nbytes = roofline.ref(2, n_nodes=256, n_edges=960, batch=8)
    assert ops == 2 * 960 * 8
    assert nbytes == 2 * (960 * 2 * 4 + 960 * 2 * 8 * 4 + 257 * 8 * 4)
    end = {"route": "ref", "n_nodes": 256, "n_edges": 960, "batch_size": 8,
           "bfs_levels": 2, "nb_steps": 0, "block_v": 0, "block_e": 0}
    secs = roofline.frontier_seconds("TPU v5 lite", end)
    assert secs == pytest.approx(max(ops / 197e12, nbytes / 819e9))
    assert roofline.frontier_seconds("TPU v5 lite",
                                     dict(end, route="flat")) is None
    with pytest.raises(KeyError):
        roofline.frontier_seconds("cpu", end)


def _run(epoch_stats, end_stats, trace=True):
    """A window of one job whose epoch program spent 2 s on the device."""
    dev = tracing.DeviceSummary(
        "/device:TPU:0", 3e9,
        {"jit_epoch_step/fusion:fusion": 1.5e9,
         "jit_epoch_step/while:while": 0.5e9,
         "jit_phase1_diameter/fusion:fusion": 1e9}, [])
    anns = [(i * 1e9, (i + 1) * 1e9, "phase.epoch", s)
            for i, s in enumerate(epoch_stats)]
    anns.append((9e9, 9e9, "run.end", end_stats))
    return {"jobs": [{"tau": 512}], "setup_s": 1.0, "window_s": 10.0,
            "trace": tracing.TraceSummary(10e9, [dev], []) if trace else None,
            "phases": anns}


def _ev(start, end, name):
    return types.SimpleNamespace(start_ns=start, duration_ns=end - start,
                                 name=name)


def _profile(ops, window=(10, 90)):
    """A profile with the bench window on the host and ``ops`` on the
    ``XLA Ops`` line of one TPU, all in the epoch program."""
    line = types.SimpleNamespace
    host = types.SimpleNamespace(name="/host:CPU", lines=[line(
        name="python", events=[_ev(*window, tracing.WINDOW)])])
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        line(name="XLA Ops", events=ops),
        line(name="XLA Modules", events=[_ev(0, 100, "jit_epoch_step(1)")])])
    return types.SimpleNamespace(planes=[host, dev])


# XLA Ops event names as a v5e trace of road256.bc gives them (cut short)
_GATHER = ("%fusion.379 = f32[261376,64]{1,0:T(8,128)} fusion(f32[65537,64]"
           "{1,0:T(8,128)S(1)} %broadcast_select_fusion.18, s32[262144]"
           "{0:T(1024)S(1)} %pad_clamp_fusion.12), kind=kCustom")
_SCATTER = ("%fusion.381 = f32[65537,64]{1,0:T(8,128)S(1)} fusion(s32[261376]"
            "{0:T(1024)S(1)} %get-tuple-element.2342, f32[261376,64] "
            "%compare_select_fusion.40), kind=kCustom")
_WALK = ("%while.337 = (s32[64]{0:T(128)}, f32[64]{0:T(128)S(1)}, s32[261376],"
         " s32[65537,64], f32[65537,64]{1,0:T(8,128)S(1)}) while(...)")
_SORT = ("%sort.2 = (s32[261376]{0:T(1024)S(1)}, s32[261376]{0:T(1024)S(1)}) "
         "sort(s32[261376]{0:T(1024)S(1)} %copy-done.7)")


def test_frontier_ops_are_the_edge_sized_arrays():
    for name in (_GATHER, _SCATTER):
        assert roofline.frontier_op(name, 261_120, 64)
    for name in (_WALK, _SORT):
        assert not roofline.frontier_op(name, 261_120, 64)
    assert not roofline.frontier_op(_GATHER, 261_120, 32)   # another B
    # the node-blocked kernel's (1, edge slots) id operands
    assert roofline.frontier_op(
        "%custom-call.3 = f32[65792,64] custom-call(s32[1,785408] %p)",
        261_120, 64)


def test_frontier_device_s_reads_the_epoch_programs_expansions():
    ops = [_ev(0, 80, "%while.326 = (s32[]) while(...)"),
           # clipped to the window: 10..30
           _ev(5, 30, _GATHER), _ev(40, 50, _SCATTER),
           _ev(50, 60, _SORT),
           # after the window
           _ev(95, 99, _SCATTER)]
    prof = _profile(ops)
    assert roofline.frontier_device_s(
        prof, n_edges=261_120, batch=64) == pytest.approx(30e-9)
    # the same operations in another module, or none of them: nothing
    assert roofline.frontier_device_s(
        prof, n_edges=261_120, batch=64, module="jit_phase1") is None
    assert roofline.frontier_device_s(
        _profile(ops[3:4]), n_edges=261_120, batch=64) is None


def test_level_ms_and_frontier_roofline_share_read_the_counters(
        monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: [
        types.SimpleNamespace(device_kind="TPU v5 lite")])
    # the expansion's own device time in the window: 0.5 of the epoch
    # program's 2 s
    monkeypatch.setattr(roofline, "read_frontier_device_s",
                        lambda _dir, **kw: 0.5)
    end = {"route": "ref", "n_nodes": 65_536, "n_edges": 261_120,
           "batch_size": 64, "block_v": 0, "block_e": 0,
           "bfs_levels": 400, "nb_steps": 0}
    run = _run([{"epoch": 1, "bfs_levels": 150, "nb_steps": 0},
                {"epoch": 2, "bfs_levels": 250, "nb_steps": 0}], end)
    level = harness.load_module("metrics", "level_ms")
    share = harness.load_module("metrics", "frontier_roofline_share")
    assert level.read(run) == pytest.approx(2000.0 / 400)
    _, nbytes = roofline.ref(400, n_nodes=65_536, n_edges=261_120, batch=64)
    assert share.read(run) == pytest.approx(100 * nbytes / 819e9 / 0.5)
    assert 0 < share.read(run) <= 100
    # a program without the counters (the parent) and an untraced run
    # leave nothing to read
    old = _run([{"epoch": 1}], {"route": "ref"})
    for metric in (level, share):
        assert metric.read(old) is None
        assert metric.read(_run([], {"route": "ref"}, trace=False)) is None
    # nor does a trace that holds no expansion
    monkeypatch.setattr(roofline, "read_frontier_device_s",
                        lambda _dir, **kw: None)
    assert share.read(run) is None


def test_a_traced_run_leaves_its_work_counts(tmp_path):
    from repro.core import AdaptiveConfig, grid_graph, run_adaptive

    g = grid_graph(16, 16)
    cfg = AdaptiveConfig(eps=0.05, delta=0.1, n0_base=64, max_epochs=2,
                         sample_batch_size=16)
    run_adaptive(g, config=cfg, key=jax.random.PRNGKey(0))     # warm
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        res = run_adaptive(g, config=cfg, key=jax.random.PRNGKey(1))
    jax.profiler.stop_trace()
    path = tracing.find_xplane(str(tmp_path))
    anns = phases.read(path)
    # the CPU's trace holds no TPU plane: no frontier time to read
    assert roofline.read_frontier_device_s(tmp_path, n_edges=960,
                                           batch=16) is None
    run = {"jobs": [{"tau": res.tau}], "trace": object(), "phases": anns}
    epochs = phases.named(run, "phase.epoch")
    (end,) = phases.named(run, "run.end")
    assert [s["bfs_levels"] for s in epochs] == \
        [st.bfs_levels for st in res.stats]
    assert all(s["bfs_levels"] > 0 and s["nb_steps"] == 0 for s in epochs)
    assert end["bfs_levels"] == sum(s["bfs_levels"] for s in epochs)
    assert (end["route"], end["batch_size"], end["n_nodes"],
            end["n_edges"], end["block_v"], end["block_e"]) == \
        (res.route, 16, 256, 960, 0, 0)
