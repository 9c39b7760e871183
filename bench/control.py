"""Put the plain reference sampler in the program's place and compare it
as the benchmark compares a job: the control of ``correct``.

    python3 bench/control.py --workload kron18.bc --seeds 1 2 3
    python3 bench/control.py --workload kron18.bc --seeds 1 --control none
    python3 bench/control.py --workload kron18.bc --seeds 5 --precision-shift 60

With the configuration's ``control`` switched on (the default) every
seed should fail the comparison; with ``--control none`` the plain
reference should pass it.  ``--precision-shift PAIRS`` instead measures
how far the sampler's exact path law moves when distances and path
counts are held in bfloat16, not float32, over PAIRS connected pairs
drawn from each seed.  The benchmark's own runs never run this.  Host
only (NumPy and SciPy); it needs no chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def control_job(exp, edges, n, seed: int, control):
    """One job's answer from the reference sampler, as the program's job
    would give it, at the cell's own size and number of samples."""
    from bench import reference
    rg = reference.build(edges, n)
    rng = np.random.default_rng([seed % (1 << 64), 11])
    scores, tau = reference.sample_scores(rg, exp.samples_lo, rng,
                                          control=control)
    _, best = reference.sampled_pair_moments(rg, rng, 4)
    # the reference's own double-sweep bound: 2 x eccentricity + 1
    vd = 2 * reference.vertex_diameter_lower(rg, best) - 1
    return {"scores": scores, "tau": tau, "vertex_diameter": vd,
            "batch_size": 1}


def precision_shift(edges, n, seed: int, pairs: int) -> dict:
    """The sampler's exact path law in float32 and in bfloat16 over the
    same connected pairs: the moved share of the inside-vertex mass, the
    largest move of one vertex's expected score, and the ratio of the
    expected counts of the 16 highest-degree vertices."""
    import ml_dtypes

    from bench import reference
    rg = reference.build(edges, n)
    rng = np.random.default_rng([seed % (1 << 64), 13])
    live = np.nonzero(rg.deg > 0)[0]
    law = {np.float32: np.zeros(n), ml_dtypes.bfloat16: np.zeros(n)}
    drawn = done = 0
    while done < pairs:
        s, t = (int(v) for v in rng.choice(live, 2, replace=False))
        drawn += 1
        p32 = reference.path_visit_probabilities(rg, s, t, np.float32)
        if p32 is None:
            continue
        law[np.float32] += p32
        law[ml_dtypes.bfloat16] += reference.path_visit_probabilities(
            rg, s, t, ml_dtypes.bfloat16)
        done += 1
    f32, b16 = law[np.float32], law[ml_dtypes.bfloat16]
    # a uniform ordered pair of distinct vertices is one of these with
    # probability live(live - 1) / n(n - 1) times the connected share
    weight = (live.size * (live.size - 1)) / (n * (n - 1)) * done / drawn
    hubs = np.argsort(-rg.deg)[:16]
    return {"pairs": done, "moved_share": float(np.abs(f32 - b16).sum()
                                                / f32.sum()),
            "max_score_move": float(np.abs(f32 - b16).max() / done
                                    * weight),
            "hub_ratio": float(b16[hubs].sum() / f32[hubs].sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", default=None,
                    help="'none' for the plain reference; default: the "
                         "configuration's control")
    ap.add_argument("--precision-shift", type=int, default=0,
                    metavar="PAIRS")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(HERE))
    from bench import check, harness

    cell = harness.resolve_cell(harness.load_spec(), args.workload)
    control = cell.config["control"] if args.control is None else (
        None if args.control == "none" else args.control)
    edges, n = harness.build_graph(cell.config)
    for seed in args.seeds:
        t0 = time.perf_counter()
        if args.precision_shift:
            out = precision_shift(edges, n, seed, args.precision_shift)
            print(json.dumps({"workload": cell.name, "seed": seed, **out,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
            continue
        exp = harness.expected(cell.config, edges, n, seed)
        job = control_job(exp, edges, n, seed, control)
        nums = check.compare(job, exp)
        table, ok = check.judge([nums], cell.config["limits"])
        print(json.dumps({"workload": cell.name, "control": control,
                          "seed": seed, "correct": ok, "checks": table,
                          "seconds": time.perf_counter() - t0}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
