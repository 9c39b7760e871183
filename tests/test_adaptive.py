"""End-to-end KADABRA: guarantee validation + SPMD lane (subprocess)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (AdaptiveConfig, brandes_numpy, erdos_renyi_graph,
                        from_edge_list, grid_graph, run_fixed_sampling,
                        run_kadabra)


def _small_world(seed=0, n=60):
    import networkx as nx
    G = nx.connected_watts_strogatz_graph(n, 6, 0.3, seed=seed)
    return from_edge_list(np.array(G.edges()), n), G


def test_kadabra_single_device_guarantee():
    g, _ = _small_world()
    eps = 0.05
    res = run_kadabra(g, eps=eps, delta=0.1)
    exact = brandes_numpy(g)
    err = np.abs(res.btilde - exact)
    # with delta=0.1 the max error exceeds eps with prob < 10%; a fixed
    # seed makes this deterministic in CI
    assert err.max() < eps, f"max err {err.max():.4f} >= eps {eps}"
    assert res.tau > 0 and res.n_epochs >= 1
    assert res.converged
    # estimates are a probability-normalized frequency vector
    assert (res.btilde >= 0).all() and (res.btilde <= 1).all()


def test_explicit_eps_delta_override_provided_config():
    """Regression: explicit eps/delta kwargs must override a provided
    config (the old `if config is None: replace(...)` guard only fired
    when the replace was a no-op, silently ignoring explicit kwargs).
    The override must land in the KadabraParams: omega is a direct
    function of (vertex diameter, eps, delta)."""
    from repro.core import compute_omega
    g, _ = _small_world()
    cfg = AdaptiveConfig(eps=0.05, delta=0.1, n0_base=50)
    over = run_kadabra(g, config=cfg, eps=0.2, delta=0.3)
    assert over.omega == pytest.approx(
        float(compute_omega(over.vertex_diameter, 0.2, 0.3)))
    # and NOT the config's (eps, delta)
    assert over.omega != pytest.approx(
        float(compute_omega(over.vertex_diameter, cfg.eps, cfg.delta)))
    # partial override: only eps passed, delta falls back to the config's
    partial_over = run_kadabra(g, config=cfg, eps=0.2)
    assert partial_over.omega == pytest.approx(
        float(compute_omega(partial_over.vertex_diameter, 0.2, cfg.delta)))
    # no kwargs: the config is used untouched
    base = run_kadabra(g, config=cfg)
    assert base.omega == pytest.approx(
        float(compute_omega(base.vertex_diameter, cfg.eps, cfg.delta)))


def test_kadabra_adaptivity_tracks_instance_difficulty():
    """Paper Table II behavior: #samples adapts to the instance.

    A near-clique (all betweenness ~ 0) stops far earlier than both its
    omega cap and a concentrated high-diameter grid at the same (eps,
    delta): the f/g rule reads the observed counts, a fixed-size scheme
    cannot.
    """
    import networkx as nx
    K = nx.complete_graph(40)
    g_easy = from_edge_list(np.array(K.edges()), 40)
    cfg = AdaptiveConfig(eps=0.1, delta=0.1, n0_base=50)
    res_easy = run_kadabra(g_easy, config=cfg)
    assert res_easy.converged
    # the adaptive rule (not the cap) fired: at the deciding epoch the
    # aggregated tau was strictly below omega and f/g were below eps
    # (the final tau also counts the in-flight frame flushed after the
    # stop — the paper's Alg. 2 has the same property)
    decided = res_easy.stats[-1]
    assert decided.tau < res_easy.omega
    assert decided.max_f < cfg.eps and decided.max_g < cfg.eps

    g_hard = grid_graph(20, 10)
    res_hard = run_kadabra(g_hard, config=cfg)
    assert res_hard.converged
    # harder instance (high diameter, concentrated betweenness) needs more
    # samples — adaptivity responds to the input, the cap alone would not
    assert res_hard.tau > 1.5 * res_easy.tau


def test_kadabra_high_diameter_graph():
    g = grid_graph(12, 5)
    res = run_kadabra(g, eps=0.1, delta=0.1)
    exact = brandes_numpy(g)
    assert np.abs(res.btilde - exact).max() < 0.1


def test_sample_batch_size_resolution():
    """The B heuristic reads (V, diameter estimate): wide batches on
    low-diameter instances, narrow on high-diameter ones — and an
    explicitly requested B always wins, at any diameter."""
    from repro.core.adaptive import resolve_sample_batch_size
    assert resolve_sample_batch_size(7, 100_000, 5) == 7
    assert resolve_sample_batch_size(1, 100, 1000) == 1
    assert resolve_sample_batch_size(None, 1 << 12, 8) == 64     # R-MAT-ish
    assert resolve_sample_batch_size(None, 1 << 12, 100) == 16   # mid
    assert resolve_sample_batch_size(None, 1 << 15, 400) == 8    # grid/road
    # the config default defers to the heuristic
    assert AdaptiveConfig().sample_batch_size is None


def test_explicit_sample_batch_size_wins_end_to_end():
    """Regression: an explicit sample_batch_size must drive the run.
    On this low-diameter instance the heuristic resolves to B=64, so an
    explicit 64 reproduces the auto run bit-for-bit under the same key,
    while an explicit B=1 (a different sample stream) does not."""
    g, _ = _small_world(seed=5, n=40)
    cfg = AdaptiveConfig(eps=0.15, delta=0.1, n0_base=50)
    from repro.core.adaptive import resolve_sample_batch_size
    from repro.core.diameter import estimate_diameter
    vd = int(estimate_diameter(g).vertex_diameter)
    assert resolve_sample_batch_size(None, g.n_nodes, vd) == 64
    import dataclasses as dc
    res_auto = run_kadabra(g, config=cfg)
    res_b64 = run_kadabra(g, config=dc.replace(cfg, sample_batch_size=64))
    res_b1 = run_kadabra(g, config=dc.replace(cfg, sample_batch_size=1))
    np.testing.assert_array_equal(res_auto.btilde, res_b64.btilde)
    assert res_auto.tau == res_b64.tau
    assert not np.array_equal(res_auto.btilde, res_b1.btilde)


def test_checkpoint_resume_bit_identical(tmp_path):
    """Mid-run checkpoint/resume (the elastic-restart story): a run
    stopped after 2 epochs and resumed from its checkpoint_dir must
    reproduce the uninterrupted run bit-for-bit — phases 1-2 replay
    deterministically from the run key and the loop key is persisted
    post-split, so the sample stream continues exactly where it left
    off."""
    import dataclasses as dc
    g, _ = _small_world()
    cfg = AdaptiveConfig(eps=0.04, delta=0.1, n0_base=400)
    full = run_kadabra(g, config=cfg)
    assert full.n_epochs >= 3       # otherwise the resume resumes nothing
    ckpt = str(tmp_path / "ckpt")
    part = run_kadabra(g, config=dc.replace(cfg, max_epochs=2),
                       checkpoint_dir=ckpt, checkpoint_every=1)
    assert not part.converged and part.n_epochs == 2
    from repro.checkpoint.store import latest_step
    assert latest_step(ckpt) == 2
    res = run_kadabra(g, config=cfg, checkpoint_dir=ckpt)
    np.testing.assert_array_equal(res.btilde, full.btilde)
    assert res.tau == full.tau
    assert res.n_epochs == full.n_epochs
    assert res.converged
    # resuming a COMPLETED run must re-flush the same state, not sample
    # extra epochs (the checkpointed done flag short-circuits the loop)
    again = run_kadabra(g, config=cfg, checkpoint_dir=ckpt)
    np.testing.assert_array_equal(again.btilde, full.btilde)
    assert again.tau == full.tau and again.converged
    assert again.n_epochs == full.n_epochs


def test_fixed_sampling_baseline():
    g, _ = _small_world(seed=3)
    b = run_fixed_sampling(g, 2000)
    exact = brandes_numpy(g)
    assert np.abs(b - exact).max() < 0.06


def test_phase_breakdown_recorded():
    g, _ = _small_world(seed=4, n=40)
    res = run_kadabra(g, eps=0.1, delta=0.1)
    for phase in ("diameter", "calibration", "sampling"):
        assert res.phase_seconds[phase] >= 0.0
    assert len(res.stats) == res.n_epochs


_SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import numpy as np
    import networkx as nx
    from repro.core import AdaptiveConfig, brandes_numpy, from_edge_list, run_kadabra

    G = nx.connected_watts_strogatz_graph(60, 6, 0.3, seed=0)
    g = from_edge_list(np.array(G.edges()), 60)
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    for agg in ["hierarchical", "flat", "root"]:
        cfg = AdaptiveConfig(eps=0.05, delta=0.1, aggregation=agg)
        res = run_kadabra(g, mesh=mesh, config=cfg)
        exact = brandes_numpy(g)
        err = np.abs(res.btilde - exact).max()
        assert err < 0.05, f"{agg}: err {err}"
        assert res.converged
        print(f"OK {agg} tau={res.tau} epochs={res.n_epochs} err={err:.4f}")

    # checkpoint/resume on the SPMD lane: exercises the restore path with
    # the sharded (n_dev, ...) frame/surplus leaves re-placed through the
    # NamedSharding tuple — bit-identical to the uninterrupted run
    import dataclasses as dc
    import tempfile
    cfg = AdaptiveConfig(eps=0.03, delta=0.1)
    base = run_kadabra(g, mesh=mesh, config=cfg)
    assert base.n_epochs >= 2
    ck = tempfile.mkdtemp()
    part = run_kadabra(g, mesh=mesh, config=dc.replace(cfg, max_epochs=1),
                       checkpoint_dir=ck)
    assert not part.converged
    resumed = run_kadabra(g, mesh=mesh, config=cfg, checkpoint_dir=ck)
    np.testing.assert_array_equal(resumed.btilde, base.btilde)
    assert resumed.tau == base.tau and resumed.converged
    print("OK spmd_resume")
""")


def test_kadabra_spmd_8dev_subprocess():
    """The SPMD lane on a 2x2x2 (pod,data,model) mesh of host devices.

    Runs in a subprocess because the fake-device XLA flag must be set
    before JAX initializes (the main test process keeps 1 device).
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SPMD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert out.stdout.count("OK") == 4
