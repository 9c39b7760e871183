"""The comparison that decides ``correct``: the program and the plain
reference pass it at a small size, and the configuration's control
fails it."""
from __future__ import annotations

import numpy as np
import pytest

from bench import check, harness, reference
from bench.conftest import run_tiny, tiny_cell
from bench.generators import kronecker


def test_program_passes_the_comparison_at_a_small_size(isolated_dirs):
    result = run_tiny(tiny_cell("kron18.bc"))
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"


def test_phase1_bound_below_the_diameter_is_caught(isolated_dirs):
    # ROADMAP R1: with graph seed 1 at scale 9 the vertex phase 1 seeds
    # at is isolated, and its "upper bound" is 1
    cell = tiny_cell("kron18.bc")
    cell.config["graph"]["seed"] = 1
    result = run_tiny(cell)
    assert result["checks"]["vd_short"]["value"] > 0
    assert not result["correct"]


def _expected_for(edges, n, samples, seed=0):
    rg = reference.build(edges, n)
    rng = np.random.default_rng(seed)
    moments, best = reference.sampled_pair_moments(rg, rng, n)
    return rg, check.Expected(
        n=n, deg=rg.deg, vd_lower=reference.vertex_diameter_lower(rg, best),
        pair_mean=moments.mean, pair_var=moments.var, pair_se=moments.se,
        samples_lo=samples, samples_slack=1)


def _reference_job(rg, samples, seed, control=None):
    scores, tau = reference.sample_scores(
        rg, samples, np.random.default_rng(seed), control=control)
    return {"scores": scores, "tau": tau, "vertex_diameter": rg.n,
            "batch_size": 1}


LIMITS = {"tau_gap": 0, "vd_short": 0, "bad_counts": 0, "len_z": 6.0}


@pytest.mark.parametrize("seed", [1, 2])
def test_plain_reference_passes_the_comparison(seed):
    edges, n = kronecker.edges({"scale": 10, "edgefactor": 16, "A": 0.57,
                                "B": 0.19, "C": 0.19}, 1)
    rg, exp = _expected_for(edges, n, 400)
    _, ok = check.judge([check.compare(_reference_job(rg, 400, seed), exp)],
                        LIMITS)
    assert ok


def test_control_pairs_skip_isolated_fails():
    edges, n = kronecker.edges({"scale": 10, "edgefactor": 16, "A": 0.57,
                                "B": 0.19, "C": 0.19}, 1)
    rg, exp = _expected_for(edges, n, 400)
    nums = check.compare(
        _reference_job(rg, 400, 3, control="pairs_skip_isolated"), exp)
    assert nums["len_z"] > LIMITS["len_z"]


def test_compare_flags_each_exact_number():
    edges, n = kronecker.edges({"scale": 7, "edgefactor": 16, "A": 0.57,
                                "B": 0.19, "C": 0.19}, 1)
    rg, exp = _expected_for(edges, n, 100)
    job = _reference_job(rg, 100, 5)
    assert check.compare(job, exp)["bad_counts"] == 0
    short = dict(job, tau=99)
    assert check.compare(short, exp)["tau_gap"] == 1
    low_vd = dict(job, vertex_diameter=exp.vd_lower - 2)
    assert check.compare(low_vd, exp)["vd_short"] == 2
    altered = dict(job, scores=job["scores"].copy())
    altered["scores"][int(np.argmax(rg.deg))] += 0.4 / 100
    assert check.compare(altered, exp)["bad_counts"] == 1


def test_job_keys_take_large_seeds():
    a = harness.job_key(2**31 + 5, 1)
    assert a.dtype == np.uint32 and a.shape == (2,)
    assert np.array_equal(a, harness.job_key(2**31 + 5, 1))
    assert not np.array_equal(a, harness.job_key(2**31 + 6, 1))
    assert harness.job_key(2**40, 1).shape == (2,)


def test_every_job_of_a_run_has_a_key_of_its_own():
    keys = {tuple(harness.job_key(2**31 + 5, i)) for i in range(64)}
    assert len(keys) == 64


def test_path_law_sums_to_the_inside_vertices():
    edges, n = kronecker.edges({"scale": 8, "edgefactor": 16, "A": 0.57,
                                "B": 0.19, "C": 0.19}, 1)
    rg = reference.build(edges, n)
    s = int(np.argmax(rg.deg))
    dist = reference.bfs_dist(rg, [s])[0]
    t = int(np.argmax(dist))
    d = int(dist[t])
    p = reference.path_visit_probabilities(rg, s, t, np.float32)
    assert d >= 3
    assert p.sum() == pytest.approx(d - 1, rel=1e-9)
    assert p[s] == 0.0 and p[t] == 0.0
    # the path holds exactly one vertex of each level strictly inside
    for level in range(1, d):
        assert p[dist == level].sum() == pytest.approx(1.0, rel=1e-9)
    assert (p <= 1 + 1e-12).all() and (p >= 0).all()


def test_bfloat16_path_counts_barely_move_the_kronecker_path_law():
    # why the configuration's control is not a lower precision: on a
    # Kronecker graph bfloat16 path counts move the sampler's law by far
    # less than one job's sampling noise
    from bench import control
    edges, n = kronecker.edges({"scale": 10, "edgefactor": 16, "A": 0.57,
                                "B": 0.19, "C": 0.19}, 1)
    out = control.precision_shift(edges, n, 3, 40)
    assert out["pairs"] == 40
    assert 0 <= out["moved_share"] < 0.01
    assert abs(out["hub_ratio"] - 1) < 0.01
