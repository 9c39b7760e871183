"""The comparison that decides ``correct``: each job's answer against the
plain reference (``bench/reference.py``).

Numbers compared for one job, each with its limit from the
configuration's ``limits``:

* ``tau_gap``: samples missing from, or beyond, the stated fixed work:
  ``max_epochs`` epochs of ``n0`` samples per sampler, plus at most one
  round's surplus (``batch - 1``) per sampler and epoch.  Exact, limit 0.
* ``vd_short``: how far the phase-1 vertex-diameter bound lies below the
  reference's lower bound on the vertex diameter.  Exact, limit 0.
* ``bad_counts``: vertices whose score times tau is not a whole count in
  [0, tau] (within 1e-3), or is not 0 on a vertex of degree 0 or 1,
  which lies inside no shortest path.  Exact, limit 0.
* ``len_z``: the summed scores are the mean number of vertices inside
  the sampled paths; its distance from the reference's pair mean, in
  standard errors (the job's sampling error and the reference's).
"""
from __future__ import annotations

import dataclasses

import numpy as np

NUMBERS = ("tau_gap", "vd_short", "bad_counts", "len_z")


@dataclasses.dataclass
class Expected:
    n: int
    deg: np.ndarray
    vd_lower: int
    pair_mean: float
    pair_var: float
    pair_se: float
    samples_lo: int      # epochs x samplers x n0
    samples_slack: int   # epochs x samplers: times (batch - 1) of surplus


def compare(job: dict, exp: Expected) -> dict:
    """Numbers of one job; ``job`` holds ``scores``, ``tau``,
    ``vertex_diameter`` and ``batch_size``."""
    tau = int(job["tau"])
    hi = exp.samples_lo + exp.samples_slack * max(int(job["batch_size"]) - 1,
                                                  0)
    tau_gap = max(0, exp.samples_lo - tau) + max(0, tau - hi)
    vd_short = max(0, exp.vd_lower - int(job["vertex_diameter"]))
    scores = np.asarray(job["scores"], np.float64)
    if scores.shape != (exp.n,) or not np.isfinite(scores).all():
        return dict(tau_gap=tau_gap, vd_short=vd_short, bad_counts=exp.n,
                    len_z=float("inf"))
    counts = scores * max(tau, 1)
    whole = np.rint(counts)
    bad = ((np.abs(counts - whole) > 1e-3) | (whole < 0) | (whole > tau)
           | ((exp.deg <= 1) & (whole != 0)))
    mean_inside = float(counts.sum()) / max(tau, 1)
    se = np.sqrt(exp.pair_var / max(tau, 1) + exp.pair_se ** 2)
    len_z = abs(mean_inside - exp.pair_mean) / se
    return dict(tau_gap=int(tau_gap), vd_short=int(vd_short),
                bad_counts=int(bad.sum()), len_z=float(len_z))


def judge(per_job: list[dict], limits: dict) -> tuple[dict, bool]:
    """Worst value of each number over the jobs, each beside its limit,
    and whether every number is within its limit."""
    worst = {k: max(j[k] for j in per_job) for k in NUMBERS} if per_job \
        else {k: float("inf") for k in NUMBERS}
    table = {k: {"value": worst[k], "limit": limits[k]} for k in NUMBERS}
    ok = bool(per_job) and all(worst[k] <= limits[k] for k in NUMBERS)
    return table, ok
