"""Shared helpers of the benchmark's CPU tests: cells shrunk to a size a
test run holds, and runs of the harness that skip its look for a chip."""
from __future__ import annotations

import copy
import sys

import pytest

from bench import harness


def shrink(config: dict) -> dict:
    """The configuration at a CPU-test size: same generator, lane and
    checks, a small graph and short epochs."""
    cfg = copy.deepcopy(config)
    cfg["graph"]["scale"] = 9
    # at scale 9, graph seed 1 isolates the vertex that phase 1 seeds at
    # (ROADMAP R1); seed 2 puts it in the giant component
    cfg["graph"]["seed"] = 2
    cfg["adaptive"]["n0_base"] = 64
    return cfg


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.resolve_cell(harness.load_spec(), name)
    cell.config = shrink(cell.config)
    return cell


def run_tiny(cell: harness.Cell, seed: int = 2**31 + 17,
             seconds: float = 0.5, trace: bool = False) -> dict:
    """Drive the rest of a run on the CPU's devices."""
    import jax
    devices = jax.devices()[:cell.chips]
    return harness.run_cell(cell, seed, seconds, trace, devices,
                            log=lambda m: print(m, file=sys.stderr))


@pytest.fixture
def isolated_dirs(tmp_path, monkeypatch):
    """Traces go to a temporary directory, not the checkout."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    return tmp_path
