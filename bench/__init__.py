"""Chip benchmark of adaptive betweenness: harness, reference and readers.

Run a cell with ``python3 bench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` from the repository root; the cells, their
configurations and their metrics are named in ``BENCHMARK.json``.
"""
