"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload kron18.bc --seed 7 --seconds 10 --trace 0

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each number compared with the
plain reference beside its limit).  The same numbers are the last lines
of standard error.  With no TPU, or fewer chips than the cell asks for,
it exits with code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness

    def log(msg):
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    cell = harness.resolve_cell(harness.load_spec(), args.workload)
    harness.configure_jax()
    try:
        devices = harness.chip_devices(cell.chips)
    except harness.NoChip as e:
        log(f"{e}; nothing run")
        return 2
    log(f"cell {cell.name}: {cell.chips} chip(s) "
        f"{devices[0].device_kind}; seed {args.seed}; "
        f"{args.seconds} s window; trace {args.trace}")
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices, log=log)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
