"""The harness is driven by data, keeps to the result-line contract, and
runs only on a chip."""
from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.conftest import run_tiny, shrink

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_names_files_that_exist():
    spec = _spec()
    assert spec["command"][1].startswith(spec["paths"][0] + "/")
    for conf in spec["configs"]:
        assert NAME.match(conf["name"])
        path = os.path.join(ROOT, conf["file"])
        assert conf["file"].startswith("bench/") and os.path.isfile(path)
        with open(path) as f:
            body = json.load(f)
        assert body["reduced"] == conf["reduced"]
        assert set(body["limits"]) == {"tau_gap", "vd_short", "bad_counts",
                                       "len_z"}
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "generators", f"{body['generator']}.py"))
    for cell in spec["workloads"]:
        assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "traffic", f"{cell['traffic']}.json"))
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}
    for m in metrics:
        assert NAME.match(m["name"])
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           f"{m['name']}.py"))
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def _hashes(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            if f.endswith((".py", ".json")):
                p = os.path.join(dirpath, f)
                with open(p, "rb") as fh:
                    out[os.path.relpath(p, root)] = \
                        hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_config_cell_and_metric_are_found_by_name(tmp_path, monkeypatch):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    spec = _spec()
    before = _hashes(tmp_path / "bench")

    # a new configuration, traffic mix and metric, each a file of its own
    kron = json.loads((tmp_path / "bench/configs/kron18.json").read_text())
    small = shrink(kron)
    small["name"] = "kron9"
    (tmp_path / "bench/configs/kron9.json").write_text(json.dumps(small))
    (tmp_path / "bench/traffic/bc.again.json").write_text(
        (tmp_path / "bench/traffic/bc.json").read_text())
    (tmp_path / "bench/metrics/jobs_done.py").write_text(
        "def read(run):\n    return len(run['jobs'])\n")
    spec["configs"].append({"name": "kron9", "source": "test",
                            "file": "bench/configs/kron9.json",
                            "reduced": ["scale"], "why": "test"})
    spec["workloads"].append({"name": "kron9.bc.again", "config": "kron9",
                              "traffic": "bc.again", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "jobs_done", "unit": "jobs",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["kron9.bc.again"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "BENCH", tmp_path / "bench")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    cell = harness.resolve_cell(harness.load_spec(), "kron9.bc.again")
    assert cell.config["graph"]["scale"] == 9
    assert [m["name"] for m in cell.end_to_end] == ["job_s", "setup_s",
                                                    "jobs_done"]
    result = run_tiny(cell)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"job_s", "setup_s", "jobs_done"}
    assert result["metrics"]["jobs_done"]["value"] >= 1
    assert result["metrics"]["job_s"]["unit"] == "s"
    after = _hashes(tmp_path / "bench")
    assert {k: after[k] for k in before} == before


def test_result_line_keys(isolated_dirs):
    cell = harness.resolve_cell(harness.load_spec(), "kron18.bc")
    cell.config = shrink(cell.config)
    result = run_tiny(cell)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "window_compiles", "checks"]
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    for row in result["checks"].values():
        assert set(row) == {"value", "limit"}


def test_window_reads_the_compile_cache_but_writes_nothing():
    import jax
    key = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, key)
    try:
        with harness.no_cache_writes():
            assert getattr(jax.config, key) >= 1e9
        assert getattr(jax.config, key) == 0.0
    finally:
        jax.config.update(key, before)


def _run_command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kron18.bc",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_command_exits_nonzero_without_a_tpu():
    out = _run_command(ROOT)
    assert out.returncode == 2
    assert "{" not in out.stdout
    assert "no TPU" in out.stderr


def test_run_command_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_command(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert "{" not in out.stdout


@pytest.mark.parametrize("name", ["job_s", "setup_s", "diameter_s",
                                  "ads_samples_per_s"])
def test_host_metric_readers(name):
    jobs = [{"seconds": 2.0, "tau": 512,
             "phase_seconds": {"diameter": 0.5, "calibration": 0.25,
                               "sampling": 1.0}}] * 2
    run = {"jobs": jobs, "setup_s": 9.0, "window_s": 4.0, "trace": None}
    want = {"job_s": 2.0, "setup_s": 9.0, "diameter_s": 0.5,
            "ads_samples_per_s": 512 / 1.25}[name]
    assert harness.load_module("metrics", name).read(run) == \
        pytest.approx(want)
    empty = dict(run, jobs=[])
    if name != "setup_s":
        assert harness.load_module("metrics", name).read(empty) is None


@pytest.mark.parametrize("name", ["device_idle_share"])
def test_trace_metric_readers_read_nothing_without_a_trace(name):
    run = {"jobs": [], "setup_s": 1.0, "window_s": 1.0, "trace": None}
    assert harness.load_module("metrics", name).read(run) is None
