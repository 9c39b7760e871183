"""The control of ``correct`` on high-diameter graphs: the plain reference
sampler with its BFS distances and path counts held in bfloat16, compared
as the benchmark compares a job.

    python3 bench/control_bf16_state.py --workload road256.bc --seeds 1 2 3
    python3 bench/control_bf16_state.py --workload road256.bc --seeds 1 --control none

bfloat16 holds whole numbers only up to 256: on a graph whose shortest
paths run longer, distances past 256 round, a walk back from such a
target finds no neighbour one level closer and stops, and the sampled
paths come out short.  Every seed should then fail the comparison
(``len_z``); with ``--control none`` the plain float32 reference
(``bench/control.py``'s job) should pass it.  The benchmark's own runs
never run this.  Host only (NumPy, SciPy, ml_dtypes); it needs no chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CONTROL = "bf16_state"


def sample_scores(rg, n_samples: int, rng):
    """``reference.sample_scores`` with distances and path counts held in
    bfloat16, on the same draws: (scores, tau) as a job of the program
    would give them."""
    import ml_dtypes

    from bench import reference
    pool = np.arange(rg.n)
    counts = np.zeros(rg.n, np.float32)
    for _ in range(n_samples):
        s, t = (int(v) for v in rng.choice(pool, 2, replace=False))
        dist, sigma = reference._bfs_sigma(rg, s, t, ml_dtypes.bfloat16)
        if dist[t] == -1:
            continue
        for v in reference._walk(rg, rng, t, dist, sigma):
            counts[v] += 1
    return counts / np.float32(n_samples), n_samples


def control_job(exp, edges, n, seed: int):
    """One job's answer from the bfloat16 sampler, at the cell's size and
    number of samples (``bench/control.py`` ``control_job``'s twin)."""
    from bench import reference
    rg = reference.build(edges, n)
    rng = np.random.default_rng([seed % (1 << 64), 11])
    scores, tau = sample_scores(rg, exp.samples_lo, rng)
    _, best = reference.sampled_pair_moments(rg, rng, 4)
    vd = 2 * reference.vertex_diameter_lower(rg, best) - 1
    return {"scores": scores, "tau": tau, "vertex_diameter": vd,
            "batch_size": 1}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", default=CONTROL, choices=(CONTROL, "none"),
                    help="'none' for the plain float32 reference")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(HERE))
    from bench import check, control, harness

    cell = harness.resolve_cell(harness.load_spec(), args.workload)
    edges, n = harness.build_graph(cell.config)
    for seed in args.seeds:
        t0 = time.perf_counter()
        exp = harness.expected(cell.config, edges, n, seed)
        job = (control_job(exp, edges, n, seed) if args.control == CONTROL
               else control.control_job(exp, edges, n, seed, None))
        table, ok = check.judge([check.compare(job, exp)],
                                cell.config["limits"])
        print(json.dumps({"workload": cell.name, "control": args.control,
                          "seed": seed, "correct": ok, "checks": table,
                          "seconds": time.perf_counter() - t0}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
